"""Neural-network operators: conv, pooling, norms, activations, embedding.

Reference parity (leezu/mxnet): ``src/operator/nn/`` — Convolution
(cudnn_convolution-inl.h), FullyConnected, BatchNorm, LayerNorm, GroupNorm,
Pooling, Activation, Softmax, Dropout, Embedding — and assorted
``src/operator/tensor`` NN helpers (``pick``, ``SequenceMask``).

Design (tpu-first): everything lowers to ``jax.lax`` convolution/reduce-window
/dot primitives that XLA tiles onto the MXU; there are no per-backend kernel
variants (cuDNN/MKLDNN dispatch collapses into XLA). Layouts accept the
reference's NCHW default but NHWC is supported and preferred on TPU; XLA's
layout assignment handles the rest. Dropout draws from the splittable
threefry stream (``ndarray/random.py``), active only in autograd train mode,
matching reference mode semantics (``mxnet.autograd.is_training``).
"""
from __future__ import annotations

import functools

from typing import Any, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from .._tape import is_training
from ..base import MXNetError, getenv, register_env
from ..ndarray.ndarray import NDArray
from ..ndarray.ops import _as_nd
from ..ndarray.register import invoke, register_op
from ..ndarray import random as _random

register_env("MXNET_BN_STATS", "shifted",
             "Training BatchNorm statistics: 'shifted' (default — one "
             "fused sweep, variance about a batch-slice mean; stable "
             "for any input statistics) or 'centered' (classic "
             "two-pass; one extra full sweep over the activation).")
register_env("MXNET_CONV_S2D", "1",
             "Rewrite stride-2 small-channel NCHW stem convolutions via "
             "space-to-depth (exact; better MXU lane utilization). "
             "Set 0 to dispatch the plain convolution.")

__all__ = [
    "activation", "relu", "leaky_relu", "prelu", "elu", "selu", "gelu",
    "silu", "swish", "mish", "softrelu", "softsign", "hard_sigmoid",
    "hard_swish", "log_sigmoid",
    "softmax", "log_softmax", "masked_softmax", "masked_log_softmax",
    "fully_connected", "convolution", "deconvolution", "pooling",
    "adaptive_avg_pool2d", "batch_norm", "batch_norm_relu_conv1x1",
    "relu_conv1x1", "conv_fusion_enabled", "layer_norm", "group_norm",
    "instance_norm", "rms_norm", "l2_normalization", "lrn",
    "dropout", "embedding", "pick", "take_positions", "sequence_mask",
    "sequence_last", "sequence_reverse", "topk_mask", "smooth_l1",
    "up_sampling", "roi_pooling", "ctc_loss",
]


def _pair(v, n):
    if isinstance(v, int):
        return (v,) * n
    return tuple(v)


# ---------------------------------------------------------------------------
# Activations (reference: src/operator/nn/activation.cc, leaky_relu.cc,
# contrib gelu; python gluon.nn.activations)
# ---------------------------------------------------------------------------

@jax.custom_jvp
def _erf_gelu(x):
    return jax.nn.gelu(x, approximate=False)


@_erf_gelu.defjvp
def _erf_gelu_jvp(primals, tangents):
    """The exact GELU ``x·Φ(x)`` with ``Φ`` evaluated once for the value
    and the derivative ``Φ(x) + x·φ(x)`` together. Left to autodiff, XLA
    keeps only ``x`` and recomputes the erfc wherever the value or the
    derivative is consumed: in the prologue of the next matmul, of its
    weight gradient and in the epilogue of its input gradient, each then
    bound by the vector unit. Here the value (at ``x``'s dtype) and the
    derivative (at least float32) are formed in one place and stored,
    and the backward is one multiply."""
    (x,), (t,) = primals, tangents
    wide = jnp.promote_types(x.dtype, jnp.float32)
    # jax.nn.gelu's own operations and roundings, so y is its value bit
    # for bit: 0.5·x·erfc(z), z = -x·√½ at x's dtype
    sqrt_half = np.sqrt(0.5).astype(x.dtype)
    z = (-x * sqrt_half).astype(wide)
    erfc = lax.erfc(z)
    xw = x.astype(wide)
    y = (0.5 * xw * erfc.astype(x.dtype).astype(wide)).astype(x.dtype)
    # d/dz erfc(z) = -2/√π·exp(-z²): exp(-z²) is the erfc's own, shared
    dy = 0.5 * erfc + xw * jnp.exp(-(z * z)) * (
        float(sqrt_half) / np.sqrt(np.pi))
    # stored as they are: fused into their consumers they would be
    # recomputed in each of them
    y, dy = lax.optimization_barrier((y, dy))
    return y, (t.astype(wide) * dy).astype(t.dtype)


def _gelu(x, approximate: bool = False):
    """The one GELU of the op library: the tanh form, or the exact form
    (``x·Φ(x)``) with its own derivative rule."""
    if approximate:
        return jax.nn.gelu(x, approximate=True)
    return _erf_gelu(x)


_ACT_FNS = {
    "relu": jax.nn.relu,
    "sigmoid": jax.nn.sigmoid,
    "log_sigmoid": jax.nn.log_sigmoid,
    "tanh": jnp.tanh,
    "softrelu": jax.nn.softplus,
    "softplus": jax.nn.softplus,
    "softsign": jax.nn.soft_sign,
    "gelu": _gelu,
    "silu": jax.nn.silu,
    "swish": jax.nn.silu,
    "mish": jax.nn.mish,
    "identity": lambda x: x,
}


def activation(data, act_type: str = "relu"):
    """Apply a named activation (reference: ``Activation`` op)."""
    fn = _ACT_FNS[act_type]
    return invoke(f"activation_{act_type}", fn, (_as_nd(data),))


def relu(data):
    return invoke("relu", jax.nn.relu, (_as_nd(data),))


def leaky_relu(data, slope: float = 0.25, act_type: str = "leaky"):
    s = slope
    if act_type in ("leaky", "rrelu"):
        return invoke("leaky_relu", lambda x: jax.nn.leaky_relu(x, s),
                      (_as_nd(data),))
    if act_type == "elu":
        return elu(data, s)
    if act_type == "gelu":
        return gelu(data)
    if act_type == "selu":
        return selu(data)
    raise ValueError(f"unknown leaky_relu act_type {act_type}")


def prelu(data, gamma):
    def impl(x, g):
        return jnp.where(x >= 0, x, g * x)
    return invoke("prelu", impl, (_as_nd(data), _as_nd(gamma)))


def elu(data, alpha: float = 1.0):
    a = alpha
    return invoke("elu", lambda x: jax.nn.elu(x, a), (_as_nd(data),))


def selu(data):
    return invoke("selu", jax.nn.selu, (_as_nd(data),))


def gelu(data, approximate: bool = False):
    ap = approximate
    return invoke("gelu", lambda x: _gelu(x, ap), (_as_nd(data),))


def silu(data):
    return invoke("silu", jax.nn.silu, (_as_nd(data),))


swish = silu


def mish(data):
    return invoke("mish", jax.nn.mish, (_as_nd(data),))


def softrelu(data):
    return invoke("softrelu", jax.nn.softplus, (_as_nd(data),))


def softsign(data):
    return invoke("softsign", jax.nn.soft_sign, (_as_nd(data),))


def log_sigmoid(data):
    return invoke("log_sigmoid", jax.nn.log_sigmoid, (_as_nd(data),))


def hard_sigmoid(data, alpha: float = 0.2, beta: float = 0.5):
    a, b = alpha, beta
    return invoke("hard_sigmoid", lambda x: jnp.clip(a * x + b, 0.0, 1.0),
                  (_as_nd(data),))


def hard_swish(data):
    return invoke("hard_swish", lambda x: x * jnp.clip(x / 6.0 + 0.5, 0.0, 1.0),
                  (_as_nd(data),))


# ---------------------------------------------------------------------------
# Softmax family (reference: src/operator/nn/softmax.cc)
# ---------------------------------------------------------------------------

def softmax(data, axis: int = -1, temperature: Optional[float] = None,
            length=None):
    ax, t = axis, temperature
    if length is not None:
        return masked_softmax(data, _length_mask(data, length, axis), axis)
    def impl(x):
        if t is not None and t != 1.0:
            x = x / t
        return jax.nn.softmax(x, axis=ax)
    return invoke("softmax", impl, (_as_nd(data),))


def log_softmax(data, axis: int = -1, temperature: Optional[float] = None):
    ax, t = axis, temperature
    def impl(x):
        if t is not None and t != 1.0:
            x = x / t
        return jax.nn.log_softmax(x, axis=ax)
    return invoke("log_softmax", impl, (_as_nd(data),))


def _length_mask(data, length, axis):
    nd = _as_nd(data)
    L = nd.shape[axis]
    ln = _as_nd(length)
    def impl(l):
        ar = jnp.arange(L)
        shape = [1] * len(nd.shape)
        shape[axis] = L
        ar = ar.reshape(shape)
        ll = l.reshape(l.shape + (1,) * (len(nd.shape) - l.ndim))
        return ar < ll
    return invoke("length_mask", impl, (ln,))


def masked_softmax(data, mask, axis: int = -1):
    ax = axis
    def impl(x, m):
        neg = jnp.finfo(x.dtype).min if jnp.issubdtype(x.dtype, jnp.floating) \
            else -1e9
        x = jnp.where(m, x, neg)
        out = jax.nn.softmax(x, axis=ax)
        return jnp.where(m, out, 0.0)
    return invoke("masked_softmax", impl, (_as_nd(data), _as_nd(mask)))


def masked_log_softmax(data, mask, axis: int = -1):
    ax = axis
    def impl(x, m):
        neg = jnp.finfo(x.dtype).min
        x = jnp.where(m, x, neg)
        return jax.nn.log_softmax(x, axis=ax)
    return invoke("masked_log_softmax", impl, (_as_nd(data), _as_nd(mask)))


# ---------------------------------------------------------------------------
# FullyConnected (reference: src/operator/nn/fully_connected.cc — cuBLAS gemm;
# here an MXU matmul)
# ---------------------------------------------------------------------------

def fully_connected(data, weight, bias=None, num_hidden: Optional[int] = None,
                    no_bias: bool = False, flatten: bool = True):
    """y = x · Wᵀ + b with reference weight layout (num_hidden, in_units)."""
    fl = flatten
    inputs = [_as_nd(data), _as_nd(weight)]
    has_bias = bias is not None and not no_bias
    if has_bias:
        inputs.append(_as_nd(bias))

    def impl(x, w, *b):
        if fl and x.ndim > 2:
            x = x.reshape(x.shape[0], -1)
        y = jnp.matmul(x, w.T)
        if b:
            y = y + b[0]
        return y

    return invoke("fully_connected", impl, tuple(inputs))


# ---------------------------------------------------------------------------
# Convolution (reference: src/operator/nn/convolution.cc + cudnn autotune;
# XLA picks conv algorithms natively, so the CuDNNAlgoReg cache disappears)
# ---------------------------------------------------------------------------

_CONV_DIMNUMS = {
    ("NCW",): ("NCW", "OIW", "NCW"),
    ("NWC",): ("NWC", "WIO", "NWC"),
    ("NCHW",): ("NCHW", "OIHW", "NCHW"),
    ("NHWC",): ("NHWC", "HWIO", "NHWC"),
    ("NCDHW",): ("NCDHW", "OIDHW", "NCDHW"),
    ("NDHWC",): ("NDHWC", "DHWIO", "NDHWC"),
}


def _s2d_stem_conv(x, w, pad):
    """Space-to-depth rewrite of a stride-2 small-channel stem conv
    (NCHW, groups=1, dilation 1, odd kernel, pad=(k-1)//2): packs 2x2
    spatial parity phases into channels so the MXU sees C*4 input lanes
    instead of C (C=3 stems waste >95% of the lanes). Mathematically
    exact — the MLPerf-era ResNet trick expressed as an XLA graph rewrite
    (the reference's analog is cudnn algorithm selection). Returns None
    when the geometry doesn't apply."""
    B, C, H, W = x.shape
    O, _, KH, KW = w.shape
    q = (KH - 1) // 2
    if KH != KW or KH % 2 == 0 or any(t != (q, q) for t in pad):
        return None
    p = q
    kp = (KH + 1) // 2

    def packed_len(L):
        out = (L + 2 * p - KH) // 2 + 1
        need = 2 * (out - 1) + KH
        need += need % 2
        right = need - L - p
        return out, need, right

    outs, needs, rights = zip(*(packed_len(L) for L in (H, W)))
    if any(r < 0 for r in rights):
        return None
    xp = jnp.pad(x, ((0, 0), (0, 0), (p, rights[0]), (p, rights[1])))
    Hp, Wp = needs[0] // 2, needs[1] // 2
    x2 = xp.reshape(B, C, Hp, 2, Wp, 2).transpose(0, 1, 3, 5, 2, 4) \
        .reshape(B, C * 4, Hp, Wp)
    wp = jnp.pad(w, ((0, 0), (0, 0), (0, 2 * kp - KH), (0, 2 * kp - KW)))
    w2 = wp.reshape(O, C, kp, 2, kp, 2).transpose(0, 1, 3, 5, 2, 4) \
        .reshape(O, C * 4, kp, kp)
    y = lax.conv_general_dilated(
        x2, w2, (1, 1), [(0, 0), (0, 0)],
        dimension_numbers=("NCHW", "OIHW", "NCHW"))
    return y[:, :, :outs[0], :outs[1]]


def convolution(data, weight, bias=None, kernel=None, stride=None, dilate=None,
                pad=None, num_filter: int = 0, num_group: int = 1,
                no_bias: bool = False, layout: str = "NCHW"):
    """N-D convolution. Weight layout follows ``layout`` (OIHW for NCHW).

    Stride-2 small-channel NCHW stems (ResNet 7x7 s2 C3 and friends) are
    rewritten via space-to-depth (see ``_s2d_stem_conv``); disable with
    ``MXNET_CONV_S2D=0``.
    """
    nd_data = _as_nd(data)
    ndim = nd_data.ndim - 2
    stride = _pair(stride or 1, ndim)
    dilate = _pair(dilate or 1, ndim)
    pad = _pair(pad if pad is not None else 0, ndim)
    dn = _CONV_DIMNUMS[(layout,)]
    groups = num_group
    padding = [(p, p) for p in pad]

    inputs = [nd_data, _as_nd(weight)]
    has_bias = bias is not None and not no_bias
    if has_bias:
        inputs.append(_as_nd(bias))
    chan_axis = layout.index("C")

    s2d_ok = (ndim == 2 and layout == "NCHW" and groups == 1 and
              tuple(stride) == (2, 2) and tuple(dilate) == (1, 1) and
              getenv("MXNET_CONV_S2D", "1") != "0")

    def impl(x, w, *b):
        # no preferred_element_type upcast for bf16: the TPU MXU already
        # accumulates bf16 convs in f32 internally, and an explicit f32
        # output breaks the conv transpose rule under reverse-mode AD
        y = None
        if s2d_ok and x.shape[1] <= 8:
            y = _s2d_stem_conv(x, w, padding)
        if y is None:
            y = lax.conv_general_dilated(
                x, w, window_strides=stride, padding=padding,
                rhs_dilation=dilate, dimension_numbers=dn,
                feature_group_count=groups)
        if b:
            shape = [1] * y.ndim
            shape[chan_axis] = b[0].shape[0]
            y = y + b[0].reshape(shape)
        return y

    return invoke("convolution", impl, tuple(inputs))


def deconvolution(data, weight, bias=None, kernel=None, stride=None,
                  dilate=None, pad=None, adj=None, num_filter: int = 0,
                  num_group: int = 1, no_bias: bool = True,
                  layout: str = "NCHW"):
    """Transposed convolution (reference: src/operator/nn/deconvolution.cc)."""
    nd_data = _as_nd(data)
    ndim = nd_data.ndim - 2
    stride = _pair(stride or 1, ndim)
    dilate = _pair(dilate or 1, ndim)
    pad = _pair(pad if pad is not None else 0, ndim)
    dn = _CONV_DIMNUMS[(layout,)]
    groups = num_group
    adj = _pair(adj if adj is not None else 0, ndim)
    inputs = [nd_data, _as_nd(weight)]
    has_bias = bias is not None and not no_bias
    if has_bias:
        inputs.append(_as_nd(bias))
    chan_axis = layout.index("C")
    # output_padding (adj) extends the high side: out = (in-1)*s - 2p +
    # d*(k-1) + 1 + adj, matching the reference's Deconvolution adj param
    padding = [(d * (k - 1) - p, d * (k - 1) - p + a)
               for k, p, d, a in zip(_pair(kernel, ndim), pad, dilate, adj)] \
        if kernel is not None else [(0, 0)] * ndim

    if groups != 1:
        raise MXNetError(
            "deconvolution with num_group > 1 is not implemented; "
            "use num_group=1 or a grouped conv + resize")

    def impl(x, w, *b):
        # gradient-of-conv formulation: lhs_dilation implements the
        # stride; the kernel is spatially flipped with in/out channel
        # axes swapped (reference deconv weight layout is (in, out, k...))
        if dn[1].startswith("OI"):        # w: (in, out, spatial...)
            wk = jnp.swapaxes(w, 0, 1)    # -> (out, in, spatial...)
            spatial = tuple(range(2, wk.ndim))
        else:                             # w: (spatial..., out, in)
            wk = jnp.swapaxes(w, -1, -2)  # -> (spatial..., in, out)
            spatial = tuple(range(0, wk.ndim - 2))
        wk = jnp.flip(wk, axis=spatial)
        y = lax.conv_general_dilated(
            x, wk, window_strides=(1,) * ndim,
            padding=padding, lhs_dilation=stride, rhs_dilation=dilate,
            dimension_numbers=dn, feature_group_count=1)
        if b:
            shape = [1] * y.ndim
            shape[chan_axis] = b[0].shape[0]
            y = y + b[0].reshape(shape)
        return y

    return invoke("deconvolution", impl, tuple(inputs))


# ---------------------------------------------------------------------------
# Pooling (reference: src/operator/nn/pooling.cc → lax.reduce_window)
# ---------------------------------------------------------------------------

def pooling(data, kernel=None, pool_type: str = "max", stride=None, pad=None,
            global_pool: bool = False, count_include_pad: bool = True,
            layout: str = "NCHW"):
    nd_data = _as_nd(data)
    ndim = nd_data.ndim - 2
    spatial_axes = [i for i, c in enumerate(layout) if c not in "NC"]

    if global_pool:
        axes = tuple(spatial_axes)
        if pool_type == "max":
            return invoke("global_max_pool",
                          lambda x: jnp.max(x, axis=axes, keepdims=True),
                          (nd_data,))
        return invoke("global_avg_pool",
                      lambda x: jnp.mean(x, axis=axes, keepdims=True),
                      (nd_data,))

    kernel = _pair(kernel, ndim)
    stride = _pair(stride or kernel, ndim)
    pad = _pair(pad if pad is not None else 0, ndim)

    window = [1] * nd_data.ndim
    strides = [1] * nd_data.ndim
    padding = [(0, 0)] * nd_data.ndim
    for ax, k, s, p in zip(spatial_axes, kernel, stride, pad):
        window[ax], strides[ax], padding[ax] = k, s, (p, p)
    window, strides = tuple(window), tuple(strides)
    pt, cip = pool_type, count_include_pad

    def impl(x):
        if pt == "max":
            init = -jnp.inf if jnp.issubdtype(x.dtype, jnp.floating) \
                else jnp.iinfo(x.dtype).min
            return lax.reduce_window(x, init, lax.max, window, strides, padding)
        if pt in ("avg", "sum"):
            s = lax.reduce_window(x, 0.0, lax.add, window, strides, padding)
            if pt == "sum":
                return s
            if cip:
                denom = 1
                for k in kernel:
                    denom *= k
                return s / denom
            ones = jnp.ones_like(x)
            cnt = lax.reduce_window(ones, 0.0, lax.add, window, strides, padding)
            return s / cnt
        if pt == "lp":
            s = lax.reduce_window(jnp.abs(x) ** 2, 0.0, lax.add, window,
                                  strides, padding)
            return jnp.sqrt(s)
        raise ValueError(f"unknown pool_type {pt}")

    return invoke(f"pooling_{pt}", impl, (nd_data,))


def adaptive_avg_pool2d(data, output_size: Union[int, Tuple[int, int]] = 1,
                        layout: str = "NCHW"):
    """contrib.AdaptiveAvgPooling2D analog (common for squeeze-excite)."""
    out = _pair(output_size, 2)
    nd_data = _as_nd(data)
    h_ax, w_ax = layout.index("H"), layout.index("W")
    H, W = nd_data.shape[h_ax], nd_data.shape[w_ax]
    if H % out[0] or W % out[1]:
        raise ValueError("adaptive pool requires divisible spatial dims")
    kh, kw = H // out[0], W // out[1]

    def impl(x):
        window = [1] * x.ndim
        window[h_ax], window[w_ax] = kh, kw
        s = lax.reduce_window(x, 0.0, lax.add, tuple(window), tuple(window),
                              [(0, 0)] * x.ndim)
        return s / (kh * kw)

    return invoke("adaptive_avg_pool2d", impl, (nd_data,))


# ---------------------------------------------------------------------------
# Normalization (reference: batch_norm.cc, layer_norm.cc w/ fast CUDA path,
# group_norm.cc, instance_norm.cc, l2_normalization.cc)
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1, 2))
def _bn_train_core(red_axes, eps, centered_stats, x, g, b, shift):
    out, mean, var, _, _ = _bn_train_math(red_axes, eps, centered_stats,
                                          x, g, b, shift)
    return out, mean, var


def _bn_train_math(red_axes, eps, centered_stats, x, g, b, shift):
    """Batch-stat forward.

    Default (``centered_stats=False``): ONE fused f32 sweep computes
    E[x-s] and E[(x-s)^2] about ``shift`` (the layer's running mean —
    already an op input, so the reduction starts immediately; ANY
    x-derived shift was measured to serialize a pre-pass and cost
    15-20% of a ResNet-50 step). The naive unshifted one-pass
    E[x^2]-E[x]^2 catastrophically cancels for large-mean inputs; the
    shift bounds the cancellation by |E[x]-shift|/std, which the gluon
    layer keeps ~0 by passing its stat-shift buffer (the PREVIOUS
    batch's mean) and using centered stats for the one virgin-shift
    forward (the fix for the round-2 advisor cold-start finding).
    Exact in infinite precision regardless of shift.

    ``centered_stats=True`` (``MXNET_BN_STATS=centered``): classic
    mean-then-E[(x-m)^2] — unconditionally stable, but the variance
    reduction serializes after the mean, costing one extra full sweep
    over x (~7% of a ResNet-50 step on v5e).
    """
    xf = x.astype(jnp.float32)
    shape = [1] * x.ndim
    for i in range(x.ndim):
        if i not in red_axes:
            shape[i] = x.shape[i]
    if centered_stats:
        mean = jnp.mean(xf, axis=red_axes)
        centered = xf - mean.reshape(shape)
        var = jnp.mean(centered * centered, axis=red_axes)
    else:
        s = lax.stop_gradient(shift.astype(jnp.float32))
        centered = xf - s.reshape(shape)
        mean_c = jnp.mean(centered, axis=red_axes)
        m2 = jnp.mean(centered * centered, axis=red_axes)
        var = jnp.maximum(m2 - mean_c * mean_c, 0.0)
        mean = mean_c + s
    inv = lax.rsqrt(var + eps)
    xhat = (xf - mean.reshape(shape)) * inv.reshape(shape)
    out = (xhat * g.astype(jnp.float32).reshape(shape)
           + b.astype(jnp.float32).reshape(shape)).astype(x.dtype)
    return out, mean, var, shape, inv


def _bn_train_fwd(red_axes, eps, centered_stats, x, g, b, shift):
    out, mean, var, shape, inv = _bn_train_math(
        red_axes, eps, centered_stats, x, g, b, shift)
    # residuals: x (original dtype) + per-channel stats; xhat is
    # recomputed in bwd (one fused elementwise op) to halve live memory
    return (out, mean, var), (x, g, mean, inv, tuple(shape), shift)


def _bn_train_bwd(red_axes, eps, centered_stats, res, cots):
    """Fused BN backward (the cudnn BatchNormalizationBackward recipe):
    dx = g*inv*(dy - db/N - xhat*dg/N), one stat sweep + one apply sweep.
    Direct cotangents on the mean/var outputs (normally zero — the layer
    consumes them outside the tape) are folded into the same pass."""
    x, g, mean, inv, shape, shift = res
    dy, dmean, dvar = cots
    xf = x.astype(jnp.float32)
    dyf = dy.astype(jnp.float32)
    n = 1
    for i in red_axes:
        n *= x.shape[i]
    xhat = (xf - mean.reshape(shape)) * inv.reshape(shape)
    dg = jnp.sum(dyf * xhat, axis=red_axes)
    db = jnp.sum(dyf, axis=red_axes)
    gf = g.astype(jnp.float32)
    dx = (gf * inv).reshape(shape) * (
        dyf - (db / n).reshape(shape) - xhat * (dg / n).reshape(shape))
    if getattr(dmean, "dtype", None) != jax.dtypes.float0:
        dx = dx + (dmean.astype(jnp.float32) / n).reshape(shape)
    if getattr(dvar, "dtype", None) != jax.dtypes.float0:
        dx = dx + (dvar.astype(jnp.float32) * (2.0 / n)).reshape(shape) \
            * (xhat / inv.reshape(shape))
    return (dx.astype(x.dtype), dg.astype(g.dtype), db.astype(g.dtype),
            jnp.zeros_like(shift))  # shift (stop_gradient) gets no grad


_bn_train_core.defvjp(_bn_train_fwd, _bn_train_bwd)


def batch_norm(data, gamma, beta, running_mean, running_var,
               eps: float = 1e-5, momentum: float = 0.9,
               fix_gamma: bool = False, use_global_stats: bool = False,
               axis: int = 1, training: Optional[bool] = None,
               stats: Optional[str] = None, shift=None):
    """BatchNorm forward. Returns (out, batch_mean, batch_var).

    The moving-stat update is done by the caller (gluon BatchNorm layer)
    outside the tape — the reference mutates aux states inside the op; a
    functional XLA op cannot, so the layer owns that side effect.

    Training-mode stats use a single-pass E[x]/E[x^2] reduction with f32
    accumulation and a hand-fused backward (reference: the cuDNN
    BatchNormalization kernels the reference dispatches to from
    ``src/operator/nn/batch_norm.cc``).
    """
    nd = _as_nd(data)
    ax = axis % nd.ndim  # normalize negative axis (e.g. -1 for NHWC)
    ep, fg = eps, fix_gamma
    train = is_training() if training is None else training
    use_batch_stats = train and not use_global_stats

    red_axes = tuple(i for i in range(nd.ndim) if i != ax)

    # stats: per-call override for the training statistics scheme — the
    # gluon layer forces 'centered' on its first (virgin-shift) training
    # forward so the shifted one-pass never sees a cold shift.
    # shift: explicit variance-shift vector for the one-pass stats (the
    # gluon layer passes its stat-shift buffer = the previous batch's
    # mean, always ~E[x]); defaults to the running mean for direct op
    # callers.
    if stats is None:
        stats = getenv("MXNET_BN_STATS", "shifted")
    centered_stats = stats == "centered"
    has_shift = shift is not None

    def impl(x, g, b, rm, rv, *rest):
        gg = jnp.ones_like(g) if fg else g
        if use_batch_stats:
            sh = rest[0] if has_shift else rm
            out, m, v = _bn_train_core(red_axes, ep, centered_stats,
                                       x, gg, b, sh)
            # stats return in the running-stat dtype so the layer's
            # moving-average update cannot silently promote rm/rv
            # (and thus eval-mode outputs) to f32 on a bf16-cast model
            return out, m.astype(rm.dtype), v.astype(rv.dtype)
        shape = [1] * x.ndim
        shape[ax] = x.shape[ax]
        inv = lax.rsqrt(rv + ep)
        out = (x - rm.reshape(shape)) * (inv * gg).reshape(shape) \
            + b.reshape(shape)
        return out, rm, rv

    inputs = (nd, _as_nd(gamma), _as_nd(beta),
              _as_nd(running_mean), _as_nd(running_var))
    if has_shift:
        inputs = inputs + (_as_nd(shift),)
    return invoke("batch_norm", impl, inputs)


# ---------------------------------------------------------------------------
# Prologue-fused 1x1 convolution (TPU bandwidth optimization): the BN
# apply + ReLU run on the VMEM tile as the consuming conv reads it, so
# the activated tensor never exists in HBM.  The reference materializes
# every Convolution->BatchNorm->Activation junction (convolution.cc /
# batch_norm.cc dispatch per-op); on TPU the ResNet step is HBM-bound
# (BASELINE.md bandwidth roofline) and XLA cannot fuse producers into a
# conv operand, so this is a Pallas kernel (ops/pallas/conv_fused.py).
# ---------------------------------------------------------------------------

register_env("MXNET_FUSE_BN_CONV", "0",
             "Fuse BatchNorm-apply+ReLU (or a plain ReLU) into a consuming "
             "1x1 stride-1 convolution as one Pallas GEMM. 0 (default) "
             "disables; 'auto' enables on a single-device TPU backend; 1 "
             "forces on (CPU runs the kernels in interpret mode). "
             "Numerically invisible (tests/test_fused_conv.py); default-off "
             "until the kernels beat XLA's convs at the gated shapes "
             "(the probe is in git history before PR 30).")

_FUSE_BN_CONV_LAST: list = [None]


def conv_fusion_enabled() -> bool:
    """Resolve MXNET_FUSE_BN_CONV OUTSIDE traced closures (graph-knob
    contract: a toggle bumps the gluon graph epoch rather than silently
    replaying a stale executable).  'auto' restricts to single-device TPU
    backends: the Pallas call is not SPMD-partitionable under a
    multi-device pjit, and CPU interpret mode is for tests only."""
    val = str(getenv("MXNET_FUSE_BN_CONV", "0")).lower()
    if val == "auto":
        cur = (jax.default_backend() == "tpu" and jax.device_count() == 1)
    else:
        cur = val not in ("0", "false", "off")
    if _FUSE_BN_CONV_LAST[0] is None:
        _FUSE_BN_CONV_LAST[0] = cur
    elif _FUSE_BN_CONV_LAST[0] != cur:
        _FUSE_BN_CONV_LAST[0] = cur
        from ..gluon.block import invalidate_cached_graphs
        invalidate_cached_graphs()
    return cur


from ..base import register_graph_knob as _register_graph_knob  # noqa: E402
_register_graph_knob(conv_fusion_enabled)


def _bn_batch_stats(xf, red_axes, centered_stats, shift):
    """Differentiable batch mean/var — the same shifted one-pass scheme
    as _bn_train_math, but in plain jnp so autodiff carries gradients
    through the stats (the fused-conv op composes them with the Pallas
    kernel's custom VJP; XLA fuses the resulting sweeps)."""
    if centered_stats:
        mean = jnp.mean(xf, axis=red_axes)
        centered = xf - mean.reshape([1, -1] + [1] * (xf.ndim - 2))
        var = jnp.mean(centered * centered, axis=red_axes)
        return mean, var
    s = lax.stop_gradient(shift.astype(jnp.float32))
    sh = s.reshape([1, -1] + [1] * (xf.ndim - 2))
    centered = xf - sh
    mean_c = jnp.mean(centered, axis=red_axes)
    m2 = jnp.mean(centered * centered, axis=red_axes)
    var = jnp.maximum(m2 - mean_c * mean_c, 0.0)
    return mean_c + s, var


def batch_norm_relu_conv1x1(data, gamma, beta, running_mean, running_var,
                            weight, conv_bias=None, eps: float = 1e-5,
                            fix_gamma: bool = False,
                            use_global_stats: bool = False,
                            training: Optional[bool] = None,
                            stats: Optional[str] = None, shift=None,
                            relu: bool = True):
    """``conv1x1(relu(batch_norm(data)))`` as ONE fused kernel, NCHW.

    Same statistics contract as ``batch_norm`` (axis=1 only): shifted
    one-pass batch stats (or 'centered' for the virgin step), moving-stat
    update left to the caller.  Returns ``(out, batch_mean, batch_var)``
    with out of shape (N, Co, H, W) from weight (Co, Ci, 1, 1).
    """
    from .pallas.conv_fused import fused_prologue_conv1x1
    nd = _as_nd(data)
    if nd.ndim != 4:
        raise MXNetError("batch_norm_relu_conv1x1 expects NCHW data")
    ep, fg = eps, fix_gamma
    train = is_training() if training is None else training
    use_batch_stats = train and not use_global_stats
    if stats is None:
        stats = getenv("MXNET_BN_STATS", "shifted")
    centered_stats = stats == "centered"
    has_shift = shift is not None
    has_bias = conv_bias is not None
    red_axes = (0, 2, 3)

    def impl(x, g, b, rm, rv, w, *rest):
        # optional operands ride at fixed slots: [conv_bias][shift]
        cb = rest[0] if has_bias else None
        sh_arr = rest[1 if has_bias else 0] if has_shift else rm
        gg = jnp.ones_like(g) if fg else g
        if use_batch_stats:
            mean, var = _bn_batch_stats(x.astype(jnp.float32), red_axes,
                                        centered_stats, sh_arr)
        else:
            mean = rm.astype(jnp.float32)
            var = rv.astype(jnp.float32)
        inv = lax.rsqrt(var + ep)
        scale = gg.astype(jnp.float32) * inv
        shiftv = b.astype(jnp.float32) - mean * scale
        y = fused_prologue_conv1x1(x, w, scale, shiftv, relu=relu, bias=cb)
        return y, mean.astype(rm.dtype), var.astype(rv.dtype)

    inputs = (nd, _as_nd(gamma), _as_nd(beta),
              _as_nd(running_mean), _as_nd(running_var), _as_nd(weight))
    inputs = inputs + ((_as_nd(conv_bias),) if has_bias else ())
    if has_shift:
        inputs = inputs + (_as_nd(shift),)
    return invoke("batch_norm_relu_conv1x1", impl, inputs)


def relu_conv1x1(data, weight, conv_bias=None):
    """``conv1x1(relu(data))`` as one fused Pallas GEMM (NCHW) — the
    bottleneck-epilogue junction (see ops/pallas/conv_fused.py)."""
    from .pallas.conv_fused import fused_prologue_conv1x1
    nd = _as_nd(data)
    if nd.ndim != 4:
        raise MXNetError("relu_conv1x1 expects NCHW data")
    has_bias = conv_bias is not None

    def impl(x, w, *rest):
        return fused_prologue_conv1x1(x, w, None, None, relu=True,
                                      bias=rest[0] if has_bias else None)

    inputs = (nd, _as_nd(weight)) + \
        ((_as_nd(conv_bias),) if has_bias else ())
    return invoke("relu_conv1x1", impl, inputs)


def layer_norm(data, gamma, beta, axis: int = -1, eps: float = 1e-5):
    ax, ep = axis, eps
    def impl(x, g, b):
        mean = jnp.mean(x, axis=ax, keepdims=True)
        var = jnp.var(x, axis=ax, keepdims=True)
        out = (x - mean) * lax.rsqrt(var + ep)
        shape = [1] * x.ndim
        shape[ax] = x.shape[ax]
        return out * g.reshape(shape) + b.reshape(shape)
    return invoke("layer_norm", impl,
                  (_as_nd(data), _as_nd(gamma), _as_nd(beta)))


def rms_norm_impl(x, g, axis: int = -1, eps: float = 1e-6):
    """:func:`rms_norm` over raw arrays: what a model family's pure
    functions (``gluon.model_zoo.ouro``) call inside their programs."""
    ms = jnp.mean(jnp.square(x), axis=axis, keepdims=True)
    shape = [1] * x.ndim
    shape[axis] = x.shape[axis]
    return x * lax.rsqrt(ms + eps) * g.reshape(shape)


def rms_norm(data, gamma, axis: int = -1, eps: float = 1e-6):
    """RMSNorm (beyond-reference; standard in modern LLM blocks)."""
    ax, ep = axis, eps
    def impl(x, g):
        return rms_norm_impl(x, g, ax, ep)
    return invoke("rms_norm", impl, (_as_nd(data), _as_nd(gamma)))


def group_norm(data, gamma, beta, num_groups: int = 1, eps: float = 1e-5):
    """GroupNorm over NC... layout (reference: src/operator/nn/group_norm.cc)."""
    ng, ep = num_groups, eps
    def impl(x, g, b):
        N, C = x.shape[0], x.shape[1]
        rest = x.shape[2:]
        xg = x.reshape((N, ng, C // ng) + rest)
        axes = tuple(range(2, xg.ndim))
        mean = jnp.mean(xg, axis=axes, keepdims=True)
        var = jnp.var(xg, axis=axes, keepdims=True)
        xg = (xg - mean) * lax.rsqrt(var + ep)
        x = xg.reshape(x.shape)
        shape = [1, C] + [1] * len(rest)
        return x * g.reshape(shape) + b.reshape(shape)
    return invoke("group_norm", impl,
                  (_as_nd(data), _as_nd(gamma), _as_nd(beta)))


def instance_norm(data, gamma, beta, eps: float = 1e-5):
    ep = eps
    def impl(x, g, b):
        axes = tuple(range(2, x.ndim))
        mean = jnp.mean(x, axis=axes, keepdims=True)
        var = jnp.var(x, axis=axes, keepdims=True)
        out = (x - mean) * lax.rsqrt(var + ep)
        shape = [1, x.shape[1]] + [1] * (x.ndim - 2)
        return out * g.reshape(shape) + b.reshape(shape)
    return invoke("instance_norm", impl,
                  (_as_nd(data), _as_nd(gamma), _as_nd(beta)))


def l2_normalization(data, eps: float = 1e-10, mode: str = "instance"):
    ep, md = eps, mode
    def impl(x):
        if md == "instance":
            axes = tuple(range(1, x.ndim))
        elif md == "channel":
            axes = (1,)
        else:  # spatial
            axes = tuple(range(2, x.ndim))
        n = jnp.sqrt(jnp.sum(jnp.square(x), axis=axes, keepdims=True) + ep)
        return x / n
    return invoke("l2_normalization", impl, (_as_nd(data),))


def lrn(data, alpha: float = 1e-4, beta: float = 0.75, knorm: float = 2.0,
        nsize: int = 5):
    """Local response norm (reference: src/operator/nn/lrn.cc)."""
    a, b, k, n = alpha, beta, knorm, nsize
    def impl(x):
        sq = jnp.square(x)
        # sum over channel window: pad channel axis then reduce_window
        window = [1, n] + [1] * (x.ndim - 2)
        pads = [(0, 0), (n // 2, n // 2)] + [(0, 0)] * (x.ndim - 2)
        s = lax.reduce_window(sq, 0.0, lax.add, tuple(window),
                              (1,) * x.ndim, pads)
        return x / jnp.power(k + a / n * s, b)
    return invoke("lrn", impl, (_as_nd(data),))


# ---------------------------------------------------------------------------
# Dropout (reference: src/operator/nn/dropout.cc)
# ---------------------------------------------------------------------------

def dropout(data, p: float = 0.5, mode: str = "training", axes=None,
            training: Optional[bool] = None):
    train = is_training() if training is None else training
    if (not train and mode != "always") or p <= 0.0:
        return _as_nd(data)
    rate, axs = p, axes
    key = _random.split_key()
    def impl(x):
        shape = list(x.shape)
        if axs:
            # variational dropout: mask is SHARED along the listed axes
            # (mask dim = 1 there), matching the reference's Dropout(axes=)
            for ax in axs:
                shape[ax] = 1
        keep = jax.random.bernoulli(key, 1.0 - rate, tuple(shape))
        return jnp.where(keep, x / (1.0 - rate), 0.0).astype(x.dtype)
    return invoke("dropout", impl, (_as_nd(data),))


# ---------------------------------------------------------------------------
# Embedding / indexing helpers (reference: indexing_op.cc Embedding, pick)
# ---------------------------------------------------------------------------

def embedding(data, weight, input_dim: Optional[int] = None,
              output_dim: Optional[int] = None, dtype=None,
              sparse_grad: bool = False):
    """Table lookup: out[i...] = weight[data[i...]].

    ``sparse_grad=True`` produces a row-sparse weight gradient
    (reference: Embedding's kRowSparseStorage grad — only touched rows
    are stored, feeding the lazy sparse optimizer updates)."""
    nd_idx, nd_w = _as_nd(data), _as_nd(weight)

    def impl(idx, w):
        return jnp.take(w, idx.astype(jnp.int32), axis=0)

    if not sparse_grad:
        return invoke("embedding", impl, (nd_idx, nd_w))

    from .._tape import RowSparseCot
    from ..ndarray.register import invoke_with_custom_vjp

    idx_raw = nd_idx._data
    w_shape = tuple(nd_w.shape)

    def vjp_fn(g):
        flat_idx = idx_raw.reshape(-1).astype(jnp.int32)
        vals = g.reshape((-1,) + w_shape[1:])
        return (None, RowSparseCot(flat_idx, vals, w_shape))

    return invoke_with_custom_vjp("embedding", impl, (nd_idx, nd_w),
                                  vjp_fn)


def take_positions(data, positions):
    """Gather per-batch sequence positions: (B,T,C),(B,P) -> (B,P,C)
    (gluon-nlp ``select_vectors_by_position`` — the MLM-head gather)."""
    def impl(x, pos):
        pos = pos.astype(jnp.int32)
        return jnp.take_along_axis(x, pos[:, :, None], axis=1)
    return invoke("take_positions", impl, (_as_nd(data), _as_nd(positions)))


def pick(data, index, axis: int = -1, keepdims: bool = False,
         mode: str = "clip"):
    ax, kd = axis, keepdims
    def impl(x, i):
        i = jnp.expand_dims(i.astype(jnp.int32), ax)
        out = jnp.take_along_axis(x, i, axis=ax)
        return out if kd else jnp.squeeze(out, axis=ax)
    return invoke("pick", impl, (_as_nd(data), _as_nd(index)))


# ---------------------------------------------------------------------------
# Sequence ops (reference: sequence_mask.cc / last.cc / reverse.cc — the
# building blocks of the era's long-sequence handling, SURVEY.md 5.7)
# ---------------------------------------------------------------------------

def sequence_mask(data, sequence_length=None, use_sequence_length: bool = False,
                  value: float = 0.0, axis: int = 0):
    if not use_sequence_length or sequence_length is None:
        return _as_nd(data)
    v, ax = value, axis
    nd = _as_nd(data)
    T = nd.shape[ax]
    def impl(x, sl):
        ar = jnp.arange(T)
        if ax == 0:  # (T, N, ...)
            mask = ar[:, None] < sl[None, :]
        else:        # (N, T, ...)
            mask = ar[None, :] < sl[:, None]
        mask = mask.reshape(mask.shape + (1,) * (x.ndim - 2))
        return jnp.where(mask, x, v)
    return invoke("sequence_mask", impl, (nd, _as_nd(sequence_length)))


def sequence_last(data, sequence_length=None, use_sequence_length: bool = False,
                  axis: int = 0):
    nd = _as_nd(data)
    ax = axis
    if not use_sequence_length or sequence_length is None:
        idx = nd.shape[ax] - 1
        def impl(x):
            return lax.index_in_dim(x, idx, axis=ax, keepdims=False)
        return invoke("sequence_last", impl, (nd,))
    def impl2(x, sl):
        last = (sl.astype(jnp.int32) - 1)
        if ax == 0:
            xt = jnp.moveaxis(x, 0, 1)  # (N, T, ...)
        else:
            xt = x
        idx = last.reshape((-1,) + (1,) * (xt.ndim - 1))
        out = jnp.take_along_axis(xt, idx, axis=1)
        return jnp.squeeze(out, axis=1)
    return invoke("sequence_last", impl2, (nd, _as_nd(sequence_length)))


def sequence_reverse(data, sequence_length=None,
                     use_sequence_length: bool = False, axis: int = 0):
    nd = _as_nd(data)
    ax = axis
    if not use_sequence_length or sequence_length is None:
        def impl(x):
            return jnp.flip(x, axis=ax)
        return invoke("sequence_reverse", impl, (nd,))
    T = nd.shape[ax]
    def impl2(x, sl):
        ar = jnp.arange(T)
        sl = sl.astype(jnp.int32)
        # per-batch index: reverse within [0, len), identity beyond
        if ax == 0:
            idx = jnp.where(ar[:, None] < sl[None, :],
                            sl[None, :] - 1 - ar[:, None], ar[:, None])
            return jnp.take_along_axis(
                x, idx.reshape(idx.shape + (1,) * (x.ndim - 2)), axis=0)
        idx = jnp.where(ar[None, :] < sl[:, None],
                        sl[:, None] - 1 - ar[None, :], ar[None, :])
        return jnp.take_along_axis(
            x, idx.reshape(idx.shape + (1,) * (x.ndim - 2)), axis=1)
    return invoke("sequence_reverse", impl2, (nd, _as_nd(sequence_length)))


def topk_mask(data, k: int, axis: int = -1):
    kk, ax = k, axis
    def impl(x):
        xm = jnp.moveaxis(x, ax, -1)
        thresh = jax.lax.top_k(xm, kk)[0][..., -1:]
        mask = xm >= thresh
        return jnp.moveaxis(mask, -1, ax)
    return invoke("topk_mask", impl, (_as_nd(data),))


def smooth_l1(data, scalar: float = 1.0):
    """Smooth-L1 (reference: src/operator/tensor/elemwise_unary_op)."""
    s = scalar
    def impl(x):
        s2 = s * s
        return jnp.where(jnp.abs(x) < 1.0 / s2,
                         0.5 * s2 * jnp.square(x),
                         jnp.abs(x) - 0.5 / s2)
    return invoke("smooth_l1", impl, (_as_nd(data),))


# ---------------------------------------------------------------------------
# Loss-head output ops (reference: src/operator/softmax_output.cc and
# src/operator/regression_output-inl.h). These are the symbolic-API loss
# heads: forward is the prediction; backward IGNORES the incoming output
# cotangent and injects the loss gradient directly — the reference's
# "implicit loss" contract that Module/Executor training relies on.
# ---------------------------------------------------------------------------

def _zero_cot(lab):
    """A cotangent for the label input (float0 for ints, zeros for floats)."""
    import numpy as onp
    if jnp.issubdtype(lab.dtype, jnp.integer) or lab.dtype == jnp.bool_:
        return onp.zeros(lab.shape, dtype=jax.dtypes.float0)
    return jnp.zeros_like(lab)


def softmax_output(data, label, grad_scale: float = 1.0,
                   ignore_label: float = -1.0, use_ignore: bool = False,
                   normalization: str = "null", multi_output: bool = False,
                   preserve_shape: bool = False, smooth_alpha: float = 0.0,
                   out_grad: bool = False):
    """Softmax forward with cross-entropy gradient injected on backward.

    ``multi_output``: softmax over axis 1 with label shaped like the
    remaining axes (the reference's per-position classification mode).
    """
    gs, il, ui, nrm = grad_scale, ignore_label, use_ignore, normalization
    ax = 1 if multi_output else -1
    sa = smooth_alpha

    @jax.custom_vjp
    def _core(x, lab):
        return jax.nn.softmax(x, axis=ax)

    def _fwd(x, lab):
        return _core(x, lab), (x, lab)

    def _bwd(res, g):
        x, lab = res
        prob = jax.nn.softmax(x, axis=ax)
        ncls = x.shape[ax]
        oh = jax.nn.one_hot(lab.astype(jnp.int32), ncls, dtype=x.dtype,
                            axis=ax)
        if sa:
            oh = oh * (1.0 - sa) + sa / (ncls - 1) * (1.0 - oh)
        grad = prob - oh
        valid = None
        if ui:
            valid = (lab != il).astype(x.dtype)
            grad = grad * jnp.expand_dims(valid, ax)
        if nrm == "batch":
            grad = grad / x.shape[0]
        elif nrm == "valid":
            cnt = jnp.sum(valid) if valid is not None else \
                float(lab.size)
            grad = grad / jnp.maximum(cnt, 1.0)
        return grad * gs, _zero_cot(lab)

    _core.defvjp(_fwd, _bwd)
    return invoke("softmax_output", _core, (_as_nd(data), _as_nd(label)))


def _regression_output(name, fwd_fn, grad_fn, data, label, grad_scale):
    gs = grad_scale

    @jax.custom_vjp
    def _core(x, lab):
        return fwd_fn(x)

    def _fwd(x, lab):
        return _core(x, lab), (x, lab)

    def _bwd(res, g):
        x, lab = res
        out = fwd_fn(x)
        # the reference normalizes regression grads by the label size per
        # batch row (DivNum over num_output)
        nout = max(1, int(_np_prod(x.shape[1:]) if x.ndim > 1 else 1))
        grad = grad_fn(out, lab.astype(x.dtype)) * (gs / nout)
        return grad, _zero_cot(lab)

    _core.defvjp(_fwd, _bwd)
    return invoke(name, _core, (_as_nd(data), _as_nd(label)))


def _np_prod(shape):
    n = 1
    for s in shape:
        n *= s
    return n


def linear_regression_output(data, label, grad_scale: float = 1.0):
    """out = data; grad = (out - label) (L2 loss head)."""
    return _regression_output("linear_regression_output", lambda x: x,
                              lambda o, l: o - l, data, label, grad_scale)


def mae_regression_output(data, label, grad_scale: float = 1.0):
    """out = data; grad = sign(out - label) (L1 loss head)."""
    return _regression_output("mae_regression_output", lambda x: x,
                              lambda o, l: jnp.sign(o - l),
                              data, label, grad_scale)


def logistic_regression_output(data, label, grad_scale: float = 1.0):
    """out = sigmoid(data); grad = (out - label) (logistic loss head)."""
    return _regression_output("logistic_regression_output", jax.nn.sigmoid,
                              lambda o, l: o - l, data, label, grad_scale)


def make_loss(data, grad_scale: float = 1.0, normalization: str = "null",
              valid_thresh: float = 0.0):
    """Mark ``data`` as a loss: backward injects ``grad_scale`` ones
    (reference: ``MakeLoss``), ignoring any incoming cotangent."""
    gs, nrm = grad_scale, normalization

    @jax.custom_vjp
    def _core(x):
        return x

    def _fwd(x):
        return x, (x.shape, x.dtype)

    def _bwd(res, g):
        shape, dt = res
        scale = gs / shape[0] if nrm == "batch" else gs
        return (jnp.full(shape, scale, dtype=dt),)

    _core.defvjp(_fwd, _bwd)
    return invoke("make_loss", _core, (_as_nd(data),))


# ---------------------------------------------------------------------------
# UpSampling / ROIPooling / CTC (reference: src/operator/nn/upsampling.cc,
# src/operator/roi_pooling.cc, src/operator/contrib/ctc_loss.cc)
# ---------------------------------------------------------------------------

def up_sampling(data, scale: int = 2, sample_type: str = "nearest",
                num_filter: int = 0):
    """Spatial upsample of NCHW data by an integer ``scale``.
    sample_type: 'nearest' (repeat) or 'bilinear' (jax.image.resize —
    the reference realizes bilinear as a fixed deconv kernel)."""
    nd = _as_nd(data)
    s = int(scale)

    def impl(x):
        N, C, H, W = x.shape
        if sample_type == "nearest":
            return jnp.repeat(jnp.repeat(x, s, axis=2), s, axis=3)
        if sample_type == "bilinear":
            return jax.image.resize(x, (N, C, H * s, W * s), "bilinear")
        raise MXNetError(f"unknown sample_type {sample_type!r}")

    return invoke("up_sampling", impl, (nd,))


def roi_pooling(data, rois, pooled_size, spatial_scale: float = 1.0):
    """Max pooling over regions of interest (reference ``ROIPooling``).

    data: (N, C, H, W); rois: (R, 5) of [batch_idx, x1, y1, x2, y2] in
    image coordinates (scaled by ``spatial_scale`` onto the feature map).
    Returns (R, C, ph, pw).  TPU-first formulation: every output bin is a
    masked max over the full (H, W) plane — static shapes, no gathers.
    """
    ph, pw = (pooled_size, pooled_size) if isinstance(pooled_size, int) \
        else tuple(pooled_size)
    ss = float(spatial_scale)

    def impl(x, r):
        N, C, H, W = x.shape
        batch_idx = r[:, 0].astype(jnp.int32)            # (R,)
        # quantized roi bounds on the feature map (reference rounding)
        x1 = jnp.round(r[:, 1] * ss).astype(jnp.int32)
        y1 = jnp.round(r[:, 2] * ss).astype(jnp.int32)
        x2 = jnp.round(r[:, 3] * ss).astype(jnp.int32)
        y2 = jnp.round(r[:, 4] * ss).astype(jnp.int32)
        rw = jnp.maximum(x2 - x1 + 1, 1).astype(jnp.float32)
        rh = jnp.maximum(y2 - y1 + 1, 1).astype(jnp.float32)
        bin_h = rh / ph                                  # (R,)
        bin_w = rw / pw

        iy = jnp.arange(ph)
        ix = jnp.arange(pw)
        # bin edges per roi: (R, ph[+1])
        hstart = jnp.floor(iy[None, :] * bin_h[:, None]).astype(
            jnp.int32) + y1[:, None]
        hend = jnp.ceil((iy[None, :] + 1) * bin_h[:, None]).astype(
            jnp.int32) + y1[:, None]
        wstart = jnp.floor(ix[None, :] * bin_w[:, None]).astype(
            jnp.int32) + x1[:, None]
        wend = jnp.ceil((ix[None, :] + 1) * bin_w[:, None]).astype(
            jnp.int32) + x1[:, None]

        hh = jnp.arange(H)
        ww = jnp.arange(W)
        # membership masks: (R, ph, H) and (R, pw, W)
        hmask = (hh[None, None, :] >= hstart[:, :, None]) \
            & (hh[None, None, :] < jnp.minimum(hend, H)[:, :, None])
        wmask = (ww[None, None, :] >= wstart[:, :, None]) \
            & (ww[None, None, :] < jnp.minimum(wend, W)[:, :, None])
        feats = x[batch_idx]                             # (R, C, H, W)
        neg = jnp.finfo(x.dtype).min
        # rectangle max separates into two staged masked maxes — peak
        # intermediate stays O(R*C*H*W), not O(R*C*ph*pw*H*W)
        rows = []
        for i in range(ph):
            m = jnp.where(hmask[:, i][:, None, :, None], feats, neg) \
                .max(axis=2)                             # (R, C, W)
            cells = []
            for j in range(pw):
                cells.append(jnp.where(wmask[:, j][:, None, :], m, neg)
                             .max(axis=-1))              # (R, C)
            rows.append(jnp.stack(cells, axis=-1))       # (R, C, pw)
        out = jnp.stack(rows, axis=-2)                   # (R, C, ph, pw)
        # empty bins (degenerate rois) produce 0, like the reference
        empty = ~(hmask.any(-1)[:, :, None]
                  & wmask.any(-1)[:, None, :])           # (R, ph, pw)
        return jnp.where(empty[:, None], 0.0, out).astype(x.dtype)

    return invoke("roi_pooling", impl, (_as_nd(data), _as_nd(rois)))


def ctc_loss(data, label, data_lengths=None, label_lengths=None,
             layout: str = "NTC"):
    """Functional CTC loss (reference ``nd.ctc_loss`` /
    ``_contrib_CTCLoss``); the log-domain DP lives in gluon.loss.CTCLoss."""
    from ..gluon.loss import CTCLoss as _CTC
    return _CTC(layout=layout)(data, label, data_lengths, label_lengths)


__all__ += ["softmax_output", "linear_regression_output",
            "mae_regression_output", "logistic_regression_output",
            "make_loss"]

for _name in __all__:
    register_op(_name, globals()[_name])


def arange_like(data, start: float = 0.0, step: float = 1.0, axis=None):
    """Range shaped like ``data`` (axis=None: the full shape, ravel
    order; otherwise a 1-D range matching that axis's length) —
    reference ``npx.arange_like``."""
    nd = _as_nd(data)
    if axis is None:
        shape = nd.shape
        n = nd.size
        return invoke("arange_like",
                      lambda x: (jnp.arange(n, dtype=jnp.float32) * step
                                 + start).reshape(shape), (nd,))
    n = nd.shape[axis]
    return invoke("arange_like",
                  lambda x: jnp.arange(n, dtype=jnp.float32) * step + start,
                  (nd,))


def rnn(data, parameters, state, state_cell=None, mode: str = "lstm",
        state_size: Optional[int] = None, num_layers: int = 1,
        bidirectional: bool = False, p: float = 0.0,
        state_outputs: bool = False, use_sequence_length: bool = False,
        sequence_length=None, training: Optional[bool] = None):
    """Functional fused RNN over a packed parameter vector — the
    reference's stateful ``RNN`` op (``src/operator/rnn-inl.h`` /
    ``npx.rnn``): cuDNN packed layout (all i2h/h2h weights layer-major,
    direction-minor; then all biases), TNC data, (L*D, N, H) states.

    TPU-first: unpacks the vector and runs the same hoisted-matmul
    ``lax.scan`` as ``gluon.rnn`` layers — one compiled program under
    jit, weight layout identical to the reference for checkpoint interop.
    """
    from ..gluon.rnn.rnn_layer import (_gates, _run_single_direction,
                                       _run_single_direction_varlen)

    varlen = use_sequence_length and sequence_length is not None
    if use_sequence_length and sequence_length is None:
        raise ValueError(
            "npx.rnn: use_sequence_length=True needs sequence_length")
    train = is_training() if training is None else training
    x_nd = _as_nd(data)
    params_nd = _as_nd(parameters)
    h0_nd = _as_nd(state)
    inputs = [x_nd, params_nd, h0_nd]
    if mode == "lstm":
        if state_cell is None:
            raise ValueError("lstm mode needs state_cell")
        inputs.append(_as_nd(state_cell))
    if varlen:
        inputs.append(_as_nd(sequence_length))
    H = state_size
    D = 2 if bidirectional else 1
    G = _gates(mode)
    I = x_nd.shape[2]  # noqa: E741

    # validate the packed vector length up front: a mis-sized vector
    # must error, not silently read duplicated/truncated tail data
    expected = 0
    for layer in range(num_layers):
        in_sz = I if layer == 0 else H * D
        expected += D * (G * H * in_sz + G * H * H)  # i2h + h2h weights
    expected += num_layers * D * 2 * G * H           # i2h + h2h biases
    if params_nd.size != expected:
        raise ValueError(
            f"rnn: packed parameter vector has {params_nd.size} elements, "
            f"expected {expected} for mode={mode!r} state_size={H} "
            f"num_layers={num_layers} bidirectional={bidirectional} "
            f"input size {I}")

    def impl(x, params, h0, *rest):
        rest = list(rest)
        lens = rest.pop().astype(jnp.int32) if varlen else None
        c0 = rest[0] if rest else None
        # -- unpack the cuDNN-ordered flat parameter vector
        off = 0

        def take(shape):
            nonlocal off
            n = 1
            for s in shape:
                n *= s
            seg = params[off:off + n]
            off += n
            return seg.reshape(shape)

        wi, wh, bi, bh = [], [], [], []
        for layer in range(num_layers):
            in_size = I if layer == 0 else H * D
            for d in range(D):
                wi.append(take((G * H, in_size)))
                wh.append(take((G * H, H)))
        for layer in range(num_layers):
            for d in range(D):
                bi.append(take((G * H,)))
                bh.append(take((G * H,)))

        outs = x
        h_finals, c_finals = [], []
        for layer in range(num_layers):
            dir_outs = []
            for d in range(D):
                k = layer * D + d
                h_init = h0[k]
                c_init = c0[k] if c0 is not None else None
                if varlen:
                    hs, carry = _run_single_direction_varlen(
                        mode, outs, lens, h_init, c_init, wi[k], wh[k],
                        bi[k], bh[k], reverse=(d == 1))
                else:
                    hs, carry = _run_single_direction(
                        mode, outs, h_init, c_init, wi[k], wh[k],
                        bi[k], bh[k], reverse=(d == 1))
                dir_outs.append(hs)
                h_finals.append(carry[0])
                if mode == "lstm":
                    c_finals.append(carry[1])
            outs = dir_outs[0] if D == 1 else \
                jnp.concatenate(dir_outs, axis=-1)
            if p > 0.0 and train and layer < num_layers - 1:
                from ..ndarray import random as _random
                keep = 1.0 - p
                mask = jax.random.bernoulli(
                    _random.split_key(), keep, outs.shape)
                outs = jnp.where(mask, outs / keep, 0.0).astype(outs.dtype)
        res = [outs, jnp.stack(h_finals)]
        if mode == "lstm":
            res.append(jnp.stack(c_finals))
        return tuple(res)

    out = invoke("rnn", impl, inputs)
    if not state_outputs:
        return out[0]
    return out


__all__ += ["arange_like", "rnn"]
for _name in ("arange_like", "rnn"):
    register_op(_name, globals()[_name])
