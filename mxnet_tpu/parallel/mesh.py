"""Device-mesh construction helpers.

The single mesh abstraction under all parallelism (SURVEY.md section 7
design stance). Axis names follow convention: ``dp`` (data), ``tp``
(tensor/model), ``sp`` (sequence/context), ``pp`` (pipeline stage),
``ep`` (expert).

Multi-slice (DCN) topologies (SURVEY.md section 5.8 north star — the
reference's multi-node ps-lite/DCN tier): ``make_mesh(..., slices=S)``
builds a HYBRID mesh where one axis (``dcn_axis``, default the first —
conventionally ``dp``) spans the slow DCN links between slices
slice-major, and every other axis stays inside a slice so its
collectives ride ICI. The analog of jax's
``mesh_utils.create_hybrid_device_mesh``.
"""
from __future__ import annotations

import threading
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as _np
import jax

from ..base import MXNetError

__all__ = ["make_mesh", "mesh_axes", "replicated", "shard_batch",
           "slice_groups", "kernel_mesh", "current_kernel_mesh"]

# (mesh, batch axes) the step THIS thread is tracing compiles for:
# Mosaic kernels cannot be partitioned by GSPMD, so ops that call one
# consult this and wrap the call in a shard_map (set by SPMDTrainer).
_kernel_state = threading.local()


class kernel_mesh:
    """Context manager: while active, ops that lower to a Pallas kernel
    shard_map it over ``mesh`` — batch over ``batch_axes``, heads over
    ``tp``.  SPMDTrainer enters this around its traced forward whenever
    the mesh has more than one device."""

    def __init__(self, mesh: "jax.sharding.Mesh",
                 batch_axes: Sequence[str] = ("dp",)) -> None:
        self._active = (mesh, tuple(batch_axes))
        self._prev = None

    def __enter__(self) -> "kernel_mesh":
        self._prev = current_kernel_mesh()
        _kernel_state.active = self._active
        return self

    def __exit__(self, *exc) -> None:
        _kernel_state.active = self._prev


def current_kernel_mesh():
    """(mesh, batch_axes) if a kernel_mesh context is active, else None."""
    return getattr(_kernel_state, "active", None)


def slice_groups(devices: Sequence) -> List[List]:
    """Group devices by TPU slice: ``slice_index`` when the platform
    reports one (real multi-slice pods), else ``process_index`` (one
    host per slice under ``jax.distributed``), else a single group.
    Groups come back in ascending slice order, each internally ordered
    by device id."""
    keyed: Dict[int, List] = {}
    for d in devices:
        k = getattr(d, "slice_index", None)
        if k is None:
            k = getattr(d, "process_index", 0)
        keyed.setdefault(k, []).append(d)
    return [sorted(keyed[k], key=lambda d: d.id) for k in sorted(keyed)]


def make_mesh(shape: Optional[Dict[str, int]] = None,
              devices: Optional[Sequence] = None,
              slices: Optional[int] = None,
              dcn_axis: Optional[str] = None) -> "jax.sharding.Mesh":
    """Build a Mesh from an axis-name -> size dict.

    ``make_mesh({"dp": 2, "tp": 4})`` on 8 chips. With ``shape=None`` all
    devices go on one ``dp`` axis. Sizes of ``-1`` are inferred (at most
    one). Axis order follows dict order — put the fastest-varying
    (ICI-neighbor) axis last, e.g. ``tp`` innermost.

    ``slices=S`` builds a hybrid DCN x ICI mesh: devices group into S
    slices (``slice_groups``; equal contiguous chunks when the platform
    reports no slice structure, e.g. the virtual CPU mesh), and the
    ``dcn_axis`` (default: the FIRST axis — keep it outermost) is laid
    out slice-major, so positions differing in its high-order part sit
    in different slices (DCN) while its in-slice remainder and every
    other axis stay on ICI. XLA then lowers collectives along that axis
    hierarchically (in-slice reduce + cross-slice exchange).
    """
    devices = list(devices if devices is not None else jax.devices())
    n = len(devices)
    if shape is None:
        shape = {"dp": n}
    names = list(shape.keys())
    sizes = list(shape.values())
    n_infer = sum(1 for s in sizes if s == -1)
    if n_infer > 1:
        raise MXNetError("at most one mesh axis may be -1")
    if n_infer == 1:
        known = 1
        for s in sizes:
            if s != -1:
                known *= s
        if n % known:
            raise MXNetError(f"cannot infer axis: {n} devices not divisible "
                             f"by {known}")
        sizes = [n // known if s == -1 else s for s in sizes]
    total = 1
    for s in sizes:
        total *= s
    if total != n:
        raise MXNetError(f"mesh {dict(zip(names, sizes))} needs {total} "
                         f"devices, have {n}")
    if slices is not None and slices > 1:
        arr = _hybrid_device_array(devices, names, sizes, slices, dcn_axis)
    else:
        arr = _np.asarray(devices).reshape(sizes)
    return jax.sharding.Mesh(arr, tuple(names))


def _hybrid_device_array(devices: List, names: List[str],
                         sizes: List[int], slices: int,
                         dcn_axis: Optional[str]) -> "_np.ndarray":
    """Device array for a multi-slice mesh: ``dcn_axis`` slice-major,
    everything else within-slice."""
    n = len(devices)
    axis = dcn_axis if dcn_axis is not None else names[0]
    if axis not in names:
        raise MXNetError(f"dcn_axis {axis!r} is not a mesh axis "
                         f"({names})")
    ai = names.index(axis)
    if sizes[ai] % slices:
        raise MXNetError(
            f"dcn axis {axis!r} (size {sizes[ai]}) must divide into "
            f"{slices} slices — its high-order factor IS the slice "
            "dimension")
    groups = slice_groups(devices)
    if len(groups) != slices:
        if len(groups) == 1 and n % slices == 0:
            # no slice structure reported (virtual CPU mesh, single
            # host): equal contiguous chunks stand in for slices
            flat = groups[0]
            per = n // slices
            groups = [flat[i * per:(i + 1) * per] for i in range(slices)]
        else:
            raise MXNetError(
                f"{len(groups)} device slice(s) found, asked for "
                f"{slices} — pass the full multi-slice device set or "
                "a slice count matching the platform")
    per = n // slices
    if any(len(g) != per for g in groups):
        raise MXNetError(
            f"uneven slices {[len(g) for g in groups]} — a hybrid mesh "
            "needs equal devices per slice")
    ici_sizes = list(sizes)
    ici_sizes[ai] = sizes[ai] // slices
    arr = _np.stack([_np.asarray(g, dtype=object).reshape(ici_sizes)
                     for g in groups])           # (S, ..., a/S, ...)
    arr = _np.moveaxis(arr, 0, ai)               # (..., S, a/S, ...)
    return arr.reshape(sizes)                    # merge: a slice-major


def mesh_axes(mesh: "jax.sharding.Mesh") -> Tuple[str, ...]:
    return tuple(mesh.axis_names)


def replicated(mesh: "jax.sharding.Mesh") -> "jax.sharding.NamedSharding":
    return jax.sharding.NamedSharding(mesh, jax.sharding.PartitionSpec())


def shard_batch(batch, mesh: "jax.sharding.Mesh", axis: str = "dp",
                seq_axis: Optional[str] = None):
    """Place a host batch onto the mesh, batch dim sharded over ``axis``
    (and optionally dim1 over ``seq_axis`` for sequence parallelism).

    The TPU-native replacement for ``gluon.utils.split_and_load``.
    """
    from ..ndarray.ndarray import NDArray, from_jax
    P = jax.sharding.PartitionSpec
    spec = P(axis, seq_axis) if seq_axis else P(axis)
    sharding = jax.sharding.NamedSharding(mesh, spec)

    def place(x):
        data = x._data if isinstance(x, NDArray) else x
        return from_jax(jax.device_put(data, sharding))

    if isinstance(batch, (list, tuple)):
        return type(batch)(place(b) for b in batch)
    return place(batch)
