"""Runtime-compiled user kernels — the ``mx.rtc`` analog on TPU.

Reference parity (leezu/mxnet): ``src/common/rtc.cc`` (``CudaModule``) —
users hand NVRTC a CUDA C source string at runtime, get back callable
kernels with explicit grid/block launch shapes.

Design (tpu-first): the idiomatic runtime kernel language on TPU is
**Pallas** (Python-authored, Mosaic-compiled), so ``PallasModule`` wraps a
user kernel function instead of a source string; grid/block launch
geometry maps to the Pallas ``grid`` + per-ref ``BlockSpec`` index maps.
Kernels run in interpret mode off-TPU so the same module works in tests.

    mod = mx.rtc.PallasModule(my_kernel, n_outputs=1)
    f = mod.get_kernel(out_shapes=[((1024,), 'float32')],
                       grid=(8,), in_specs=..., out_specs=...)
    y = f(x)        # NDArray in, NDArray out, autograd-transparent

``CudaModule(source)`` raises with guidance — CUDA C has no TPU target.
"""
from __future__ import annotations

from typing import Any, Callable, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from .base import MXNetError
from .ndarray.ops import _as_nd
from .ndarray.register import invoke

__all__ = ["PallasModule", "CudaModule"]


def _on_tpu() -> bool:
    return jax.devices()[0].platform == "tpu"


class PallasModule:
    """A user-authored Pallas kernel, callable over NDArrays.

    Parameters
    ----------
    kernel : callable(*in_refs, *out_refs)
        Pallas kernel body (refs follow jax.experimental.pallas).
    name : display name for profiler/debugging.
    """

    def __init__(self, kernel: Callable, name: Optional[str] = None) -> None:
        self._kernel = kernel
        self._name = name or getattr(kernel, "__name__", "pallas_kernel")

    def get_kernel(self, out_shapes: Sequence[Tuple[Tuple[int, ...], Any]],
                   grid: Optional[Tuple[int, ...]] = None,
                   in_specs: Any = None, out_specs: Any = None,
                   interpret: Optional[bool] = None,
                   vjp: Optional[Callable] = None,
                   **pallas_kwargs: Any) -> Callable:
        """Bind launch geometry; returns ``f(*ndarrays) -> NDArray(s)``.

        out_shapes: [(shape, dtype), ...] — one per kernel output ref.
        grid / in_specs / out_specs: forwarded to ``pallas_call``.
        interpret: force interpret mode (defaults to auto: off-TPU only).
        vjp: optional ``vjp(out_cot, *input_arrays) -> per-input cots``
            making the kernel autograd-capable (single-output kernels);
            without it the kernel is non-differentiable, like the
            reference's CudaModule kernels.
        """
        from jax.experimental import pallas as pl

        if interpret is None:
            interpret = not _on_tpu()
        shape_struct = [jax.ShapeDtypeStruct(s, jnp.dtype(d))
                        for s, d in out_shapes]
        single = len(shape_struct) == 1
        call_kwargs = dict(pallas_kwargs)
        if grid is not None:
            call_kwargs["grid"] = grid
        if in_specs is not None:
            call_kwargs["in_specs"] = in_specs
        if out_specs is not None:
            call_kwargs["out_specs"] = (
                out_specs[0] if single and isinstance(out_specs, (list,
                                                                  tuple))
                else out_specs)

        fn = pl.pallas_call(
            self._kernel,
            out_shape=shape_struct[0] if single else shape_struct,
            interpret=interpret, **call_kwargs)

        name = self._name

        def launch(*inputs):
            nds = [_as_nd(x) for x in inputs]
            if vjp is None:
                return invoke(f"rtc_{name}", lambda *arr: fn(*arr),
                              tuple(nds))
            from .ndarray.register import invoke_with_custom_vjp
            arrays = [n._data for n in nds]
            return invoke_with_custom_vjp(
                f"rtc_{name}", lambda *arr: fn(*arr), tuple(nds),
                lambda cot: vjp(cot, *arrays))

        launch.__name__ = name
        return launch


class CudaModule:
    """Unavailable on TPU; kept for API parity with guidance."""

    def __init__(self, *args: Any, **kwargs: Any) -> None:
        raise MXNetError(
            "CudaModule (NVRTC CUDA C) has no TPU target. Author runtime "
            "kernels with mx.rtc.PallasModule — Pallas is the TPU-native "
            "kernel language (see /opt/skills/guides/pallas_guide.md and "
            "mxnet_tpu/ops/pallas/ for examples).")
