"""Prologue-fused 1x1 convolution — the BN apply + ReLU folded into the
GEMM's operand read.

Reference parity (leezu/mxnet): the reference materializes every
``Convolution -> BatchNorm -> Activation`` junction through HBM
(``src/operator/nn/convolution.cc`` dispatches cuDNN per op;
``MXNET_SUBGRAPH_BACKEND`` fusion only covers pointwise chains).  On TPU
the ResNet-50 step is HBM-bound (BASELINE.md bandwidth roofline; the
layer probe is in git history before PR 30): every pass over an activation
tensor costs ~1/850 GB/s, and XLA cannot fuse producers into a
convolution's operand.  A 1x1 stride-1 convolution IS a GEMM, so Pallas
can: these kernels compute ``y = w @ f(x)`` where ``f`` (per-channel
affine = the BN apply, then ReLU) runs on the VMEM tile as it streams in
— the activated tensor never exists in HBM, forward or backward.

Savings per fused junction (vs the unfused chain): forward skips the
apply/ReLU write and the conv's read of it (2 HBM passes over the
activation); backward recomputes the ReLU mask and the wgrad operand
from ``x`` instead of saving ``f(x)`` (halves residual memory and skips
the separate relu-backward pass).

Kernel forms follow docs/performance.md rules: the forward contraction
is 'nn' (w's lane dim x h's sublane dim), dgrad is 'tn' (both sublane)
— MXU-native, no in-kernel transposes; wgrad contracts over the lane
(spatial) dim, the one unavoidable 'nt'.  Accumulation always runs over
the LAST grid axis (axes marked arbitrary), partials in f32 VMEM
scratch, with a no-scratch specialization when one block covers the
contraction.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _prec(dtype):
    """bf16 MXU passes for low-precision inputs, exact fp32 for f32 —
    independent of the global jax_default_matmul_precision (see
    attention.py _prec)."""
    return (jax.lax.Precision.HIGHEST if dtype == jnp.float32
            else jax.lax.Precision.DEFAULT)


def _interpret() -> bool:
    return jax.default_backend() == "cpu"


def _block(dim: int, want: int, lane: bool, interpret: bool) -> int:
    """Legal block size for a BLOCKED (ci/co) axis: must divide the dim
    exactly (these axes are contracted or accumulator-indexed — a ragged
    block would silently drop channels), and lane dims need a multiple
    of 128, sublane dims a multiple of 8.  Falls back to the whole axis."""
    if dim <= want:
        return dim
    if dim % want:
        return dim
    if interpret:
        return want
    if lane:
        return want if want % 128 == 0 else dim
    return want if want % 8 == 0 else dim


def _ceil_div(a: int, b: int) -> int:
    return -(-a // b)


def _choose_blocks(Ci, Co, M, interpret, block_co, block_m, block_ci):
    """Whole-M spatial blocks whenever VMEM allows: with m untiled the
    weight block is fetched once per co-block for the WHOLE batch (the
    grid runs batch inside co — weight-stationary), instead of once per
    (n, m) step.  Channel blocks shrink for big M to keep tiles ~1.6MB."""
    if M <= 1024:
        return (_block(Co, block_co, False, interpret), M,
                _block(Ci, block_ci, True, interpret))
    if M <= 4096:
        return (_block(Co, 128, False, interpret), M,
                _block(Ci, 128, True, interpret))
    return (_block(Co, block_co, False, interpret),
            block_m,
            _block(Ci, block_ci, True, interpret))


def fusion_profitable(N: int, Ci: int, Co: int, M: int) -> bool:
    """Traffic economics of the fused junction: the prologue saves ~2
    HBM passes over the (Ci, M) activation per sample, while the GEMM
    kernels re-read the (Co, Ci) weight once per sample (vs once total
    for XLA's batched conv).  Benefit 4*N*Ci*M bytes vs cost ~2*N*Co*Ci
    → fuse iff 2*M >= Co.  (ResNet-50 b128: stages 1-2 and stage-3 j1
    qualify — exactly where the per-stage attribution puts the time;
    stage 4 is weight-dominated and stays on XLA.)"""
    return 2 * M >= Co


def _prologue(x_ref, scale_ref, shift_ref, relu: bool):
    """f(x) on the streamed-in tile: per-channel affine (the BN apply),
    then ReLU.  x tile is (1, ci, m); scale/shift are (ci, 1) columns
    that broadcast over the spatial lanes."""
    a = x_ref[0].astype(jnp.float32)
    if scale_ref is not None:
        a = a * scale_ref[...] + shift_ref[...]
    if relu:
        a = jnp.maximum(a, 0.0)
    return a


# ---------------------------------------------------------------------------
# forward: y[n] = w @ f(x[n])   (grid co, n, m, ci — accumulate over ci;
# n INSIDE co keeps the w block resident across the whole batch)
# ---------------------------------------------------------------------------

def _fwd_kernel(*refs, n_ci: int, relu: bool, affine: bool, bias: bool,
                prec):
    refs = list(refs)
    scale_ref = refs.pop(0) if affine else None
    shift_ref = refs.pop(0) if affine else None
    x_ref, w_ref = refs.pop(0), refs.pop(0)
    bias_ref = refs.pop(0) if bias else None
    y_ref = refs.pop(0)
    h = _prologue(x_ref, scale_ref, shift_ref, relu).astype(w_ref.dtype)
    part = lax.dot_general(w_ref[...], h, (((1,), (0,)), ((), ())),
                           preferred_element_type=jnp.float32,
                           precision=prec)

    def _emit(val):
        if bias_ref is not None:
            val = val + bias_ref[...]      # (co, 1) broadcast over lanes
        y_ref[0] = val.astype(y_ref.dtype)

    if n_ci == 1:
        _emit(part)
        return
    acc_ref, = refs
    i_ci = pl.program_id(3)

    @pl.when(i_ci == 0)
    def _init():
        acc_ref[...] = part

    @pl.when(i_ci > 0)
    def _acc():
        acc_ref[...] += part

    @pl.when(i_ci == n_ci - 1)
    def _out():
        _emit(acc_ref[...])


def _fwd(x3, scale2, shift2, w, relu, interpret, bias2=None,
         block_co=256, block_m=512, block_ci=256):
    N, Ci, M = x3.shape
    Co = w.shape[0]
    affine = scale2 is not None
    # the spatial axis is never padded (a jnp.pad would cost a full HBM
    # copy of x, wiping out the fusion's savings): m is not contracted
    # here, so the ragged last block's garbage lanes land in dropped
    # output lanes
    block_co, block_m, block_ci = _choose_blocks(
        Ci, Co, M, interpret, block_co, block_m, block_ci)
    n_m, n_ci, n_co = _ceil_div(M, block_m), Ci // block_ci, Co // block_co

    kernel = functools.partial(_fwd_kernel, n_ci=n_ci, relu=relu,
                               affine=affine, bias=bias2 is not None,
                               prec=_prec(x3.dtype))
    in_specs = []
    args = []
    if affine:
        in_specs += [
            pl.BlockSpec((block_ci, 1), lambda co, n, m, ci: (ci, 0)),
            pl.BlockSpec((block_ci, 1), lambda co, n, m, ci: (ci, 0)),
        ]
        args += [scale2, shift2]
    in_specs += [
        pl.BlockSpec((1, block_ci, block_m),
                     lambda co, n, m, ci: (n, ci, m)),
        pl.BlockSpec((block_co, block_ci),
                     lambda co, n, m, ci: (co, ci)),
    ]
    args += [x3, w]
    if bias2 is not None:
        in_specs.append(
            pl.BlockSpec((block_co, 1), lambda co, n, m, ci: (co, 0)))
        args.append(bias2)
    y = pl.pallas_call(
        kernel,
        grid=(n_co, N, n_m, n_ci),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, block_co, block_m),
                               lambda co, n, m, ci: (n, co, m)),
        out_shape=jax.ShapeDtypeStruct((N, Co, M), x3.dtype),
        scratch_shapes=([] if n_ci == 1 else
                        [pltpu.VMEM((block_co, block_m), jnp.float32)]),
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(dimension_semantics=(
            "parallel", "parallel", "parallel", "arbitrary")),
    )(*args)
    return y


# ---------------------------------------------------------------------------
# dgrad: da[n] = (w^T @ dy[n]) * relu'(a)   (grid ci, n, m, co — acc over
# co; n inside ci keeps the w block batch-resident).  The mask is
# recomputed from x in the LAST co step's epilogue, so the activated
# tensor is never read from (or written to) HBM
# ---------------------------------------------------------------------------

def _dgrad_kernel(*refs, n_co: int, relu: bool, affine: bool, prec):
    if affine:
        scale_ref, shift_ref, x_ref, dy_ref, w_ref, da_ref = refs[:6]
        rest = refs[6:]
    else:
        x_ref, dy_ref, w_ref, da_ref = refs[:4]
        scale_ref = shift_ref = None
        rest = refs[4:]
    part = lax.dot_general(w_ref[...], dy_ref[0], (((0,), (0,)), ((), ())),
                           preferred_element_type=jnp.float32,
                           precision=prec)

    def _emit(val):
        if relu:
            a = _prologue(x_ref, scale_ref, shift_ref, relu=False)
            val = jnp.where(a > 0, val, 0.0)
        da_ref[0] = val.astype(da_ref.dtype)

    if n_co == 1:
        _emit(part)
        return
    acc_ref, = rest
    i_co = pl.program_id(3)

    @pl.when(i_co == 0)
    def _init():
        acc_ref[...] = part

    @pl.when(i_co > 0)
    def _acc():
        acc_ref[...] += part

    @pl.when(i_co == n_co - 1)
    def _out():
        _emit(acc_ref[...])


def _dgrad(x3, scale2, shift2, w, dy3, relu, interpret,
           block_co=256, block_m=512, block_ci=256):
    N, Ci, M = x3.shape
    Co = w.shape[0]
    affine = scale2 is not None
    # m is not contracted: ragged-last-block garbage stays in dropped
    # lanes (same no-pad rationale as _fwd)
    block_co, block_m, block_ci = _choose_blocks(
        Ci, Co, M, interpret, block_co, block_m, block_ci)
    n_m, n_ci, n_co = _ceil_div(M, block_m), Ci // block_ci, Co // block_co

    kernel = functools.partial(_dgrad_kernel, n_co=n_co, relu=relu,
                               affine=affine, prec=_prec(x3.dtype))
    in_specs = []
    args = []
    if affine:
        in_specs += [
            pl.BlockSpec((block_ci, 1), lambda ci, n, m, co: (ci, 0)),
            pl.BlockSpec((block_ci, 1), lambda ci, n, m, co: (ci, 0)),
        ]
        args += [scale2, shift2]
    in_specs += [
        pl.BlockSpec((1, block_ci, block_m),
                     lambda ci, n, m, co: (n, ci, m)),
        pl.BlockSpec((1, block_co, block_m),
                     lambda ci, n, m, co: (n, co, m)),
        pl.BlockSpec((block_co, block_ci),
                     lambda ci, n, m, co: (co, ci)),
    ]
    args += [x3, dy3, w]
    da = pl.pallas_call(
        kernel,
        grid=(n_ci, N, n_m, n_co),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, block_ci, block_m),
                               lambda ci, n, m, co: (n, ci, m)),
        out_shape=jax.ShapeDtypeStruct((N, Ci, M), jnp.float32),
        scratch_shapes=([] if n_co == 1 else
                        [pltpu.VMEM((block_ci, block_m), jnp.float32)]),
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(dimension_semantics=(
            "parallel", "parallel", "parallel", "arbitrary")),
    )(*args)
    return da


# ---------------------------------------------------------------------------
# wgrad: dw = sum_n dy[n] @ f(x[n])^T   (grid co, ci, n, m — acc over n AND m)
# f recomputed in the prologue; the ragged last m-block is lane-masked
# on both operands (m is contracted — garbage must not enter the sum)
# ---------------------------------------------------------------------------

def _wgrad_kernel(*refs, n_n: int, n_m: int, relu: bool, affine: bool,
                  m_total: int, block_m: int, prec):
    if affine:
        scale_ref, shift_ref, x_ref, dy_ref, dw_ref, acc_ref = refs
    else:
        x_ref, dy_ref, dw_ref, acc_ref = refs
        scale_ref = shift_ref = None
    i_n, i_m = pl.program_id(2), pl.program_id(3)
    h = _prologue(x_ref, scale_ref, shift_ref, relu)
    dy = dy_ref[0].astype(jnp.float32)
    if m_total % block_m:
        # m IS contracted here: the ragged last block's garbage lanes
        # (potentially NaN) must be zeroed on BOTH operands
        valid = m_total - i_m * block_m
        h = jnp.where(lax.broadcasted_iota(jnp.int32, h.shape, 1)
                      < valid, h, 0.0)
        dy = jnp.where(lax.broadcasted_iota(jnp.int32, dy.shape, 1)
                       < valid, dy, 0.0)
    cd = jnp.bfloat16 if dy_ref.dtype == jnp.bfloat16 else jnp.float32
    part = lax.dot_general(dy.astype(cd), h.astype(cd),
                           (((1,), (1,)), ((), ())),
                           preferred_element_type=jnp.float32,
                           precision=prec)
    first = jnp.logical_and(i_n == 0, i_m == 0)

    @pl.when(first)
    def _init():
        acc_ref[...] = part

    @pl.when(jnp.logical_not(first))
    def _acc():
        acc_ref[...] += part

    @pl.when(jnp.logical_and(i_n == n_n - 1, i_m == n_m - 1))
    def _out():
        dw_ref[...] = acc_ref[...].astype(dw_ref.dtype)


def _wgrad(x3, scale2, shift2, dy3, relu, interpret, out_dtype,
           block_co=256, block_m=512, block_ci=256):
    N, Ci, M = x3.shape
    Co = dy3.shape[1]
    affine = scale2 is not None
    block_co, block_m, block_ci = _choose_blocks(
        Ci, Co, M, interpret, block_co, block_m, block_ci)
    # dw blocks index the OUTPUT: both are sublane-legal already (the
    # chooser only returns 8-multiples or whole axes)
    n_m, n_ci, n_co = _ceil_div(M, block_m), Ci // block_ci, Co // block_co

    kernel = functools.partial(_wgrad_kernel, n_n=N, n_m=n_m, relu=relu,
                               affine=affine, m_total=M, block_m=block_m,
                               prec=_prec(x3.dtype))
    in_specs = []
    args = []
    if affine:
        in_specs += [
            pl.BlockSpec((block_ci, 1), lambda co, ci, n, m: (ci, 0)),
            pl.BlockSpec((block_ci, 1), lambda co, ci, n, m: (ci, 0)),
        ]
        args += [scale2, shift2]
    in_specs += [
        pl.BlockSpec((1, block_ci, block_m),
                     lambda co, ci, n, m: (n, ci, m)),
        pl.BlockSpec((1, block_co, block_m),
                     lambda co, ci, n, m: (n, co, m)),
    ]
    args += [x3, dy3]
    dw = pl.pallas_call(
        kernel,
        grid=(n_co, n_ci, N, n_m),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((block_co, block_ci),
                               lambda co, ci, n, m: (co, ci)),
        out_shape=jax.ShapeDtypeStruct((Co, Ci), out_dtype),
        scratch_shapes=[pltpu.VMEM((block_co, block_ci), jnp.float32)],
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(dimension_semantics=(
            "parallel", "parallel", "arbitrary", "arbitrary")),
    )(*args)
    return dw


# ---------------------------------------------------------------------------
# custom-vjp ops (flat (N, Ci, M) form; the public wrapper reshapes NCHW)
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7))
def _fused_core(x3, scale2, shift2, bias2, w, relu, affine, bias):
    return _fwd(x3, scale2 if affine else None,
                shift2 if affine else None, w, relu, _interpret(),
                bias2 if bias else None)


def _fused_core_fwd(x3, scale2, shift2, bias2, w, relu, affine, bias):
    y = _fused_core(x3, scale2, shift2, bias2, w, relu, affine, bias)
    return y, (x3, scale2, shift2, bias2, w)


def _fused_core_bwd(relu, affine, bias, res, dy):
    x3, scale2, shift2, bias2, w = res
    interp = _interpret()
    sc = scale2 if affine else None
    sh = shift2 if affine else None
    da = _dgrad(x3, sc, sh, w, dy, relu, interp)
    dw = _wgrad(x3, sc, sh, dy, relu, interp, w.dtype)
    if affine:
        # one fused XLA sweep over (da, x): dx + both per-channel sums
        dx = (da * scale2.reshape(1, -1, 1)).astype(x3.dtype)
        dscale = jnp.sum(da * x3.astype(jnp.float32), axis=(0, 2)) \
            .reshape(scale2.shape).astype(scale2.dtype)
        dshift = jnp.sum(da, axis=(0, 2)) \
            .reshape(shift2.shape).astype(shift2.dtype)
    else:
        dx = da.astype(x3.dtype)
        dscale = jnp.zeros_like(scale2)
        dshift = jnp.zeros_like(shift2)
    dbias = (jnp.sum(dy.astype(jnp.float32), axis=(0, 2))
             .reshape(bias2.shape).astype(bias2.dtype)
             if bias else jnp.zeros_like(bias2))
    return dx, dscale, dshift, dbias, dw


_fused_core.defvjp(_fused_core_fwd, _fused_core_bwd)


def fused_prologue_conv1x1(x, w, scale: Optional[jax.Array] = None,
                           shift: Optional[jax.Array] = None,
                           relu: bool = True,
                           bias: Optional[jax.Array] = None):
    """``y = w @ relu(x * scale + shift) + bias`` as ONE kernel, NCHW.

    x: (N, Ci, H, W); w: (Co, Ci) or (Co, Ci, 1, 1); scale/shift: (Ci,)
    per-channel affine (the BN apply) or None for a plain-ReLU prologue;
    bias: (Co,) conv bias or None.  Returns (N, Co, H, W) in x.dtype.
    Differentiable in x, w, scale, shift, bias (custom VJP; see module
    docstring for the backward shape).
    """
    N, Ci, H, W_ = x.shape
    if w.ndim == 4:
        w = w.reshape(w.shape[0], w.shape[1])
    Co = w.shape[0]
    x3 = x.reshape(N, Ci, H * W_)
    affine = scale is not None
    has_bias = bias is not None
    scale2 = (scale.astype(jnp.float32).reshape(Ci, 1) if affine
              else jnp.zeros((1, 1), jnp.float32))
    shift2 = (shift.astype(jnp.float32).reshape(Ci, 1) if affine
              else jnp.zeros((1, 1), jnp.float32))
    bias2 = (bias.astype(jnp.float32).reshape(Co, 1) if has_bias
             else jnp.zeros((1, 1), jnp.float32))
    y3 = _fused_core(x3, scale2, shift2, bias2, w, relu, affine, has_bias)
    return y3.reshape(N, Co, H, W_)
