"""``mx.runtime`` — runtime feature introspection.

Reference parity (leezu/mxnet): ``src/libinfo.cc`` / ``python/mxnet/
runtime.py`` — build-time ``USE_*`` flags surfaced as ``Features``.  Here
features are determined at import time from the live environment (which
backend jax sees, whether the native runtime library built, etc.) since
there is no compile-time feature matrix.
"""
from __future__ import annotations

from collections import OrderedDict
from typing import Dict, Iterator

__all__ = ["Feature", "Features", "feature_list", "list_env",
           "device_info"]

from .base import list_env  # noqa: E402  (env-var config surface)


class Feature:
    """One runtime feature flag (reference: ``mx.runtime.Feature``)."""

    def __init__(self, name: str, enabled: bool) -> None:
        self.name = name
        self.enabled = enabled

    def __bool__(self) -> bool:
        return self.enabled

    def __repr__(self) -> str:
        return f"[{'✔' if self.enabled else '✖'} {self.name}]"


def _detect() -> "OrderedDict[str, Feature]":
    feats: "OrderedDict[str, Feature]" = OrderedDict()

    def add(name: str, enabled: bool) -> None:
        feats[name] = Feature(name, bool(enabled))

    import jax
    platforms = {d.platform for d in jax.devices()}
    add("TPU", "tpu" in platforms)
    add("CPU", True)
    add("CUDA", "gpu" in platforms or "cuda" in platforms)

    add("BF16", True)                 # always available on XLA
    add("INT64_TENSOR_SIZE", True)
    try:
        from jax.experimental import pallas  # noqa: F401
        add("PALLAS", True)
    except Exception:   # noqa: BLE001
        add("PALLAS", False)

    try:
        from ._native import LIB
        add("NATIVE_ENGINE", LIB is not None)
    except Exception:   # noqa: BLE001
        add("NATIVE_ENGINE", False)

    add("SPARSE", True)
    add("AMP", True)
    add("RECORDIO", True)
    add("PROFILER", True)
    add("DIST_KVSTORE", True)         # ICI/DCN collectives via jax.sharding
    add("SIGNAL_HANDLER", False)
    add("OPENCV", False)              # PIL-backed decode instead
    try:
        import PIL  # noqa: F401
        add("IMAGE_IO", True)
    except Exception:   # noqa: BLE001
        add("IMAGE_IO", False)
    return feats


class Features:
    """Mapping of feature name -> :class:`Feature`
    (reference: ``mx.runtime.Features``, ``libinfo_features``)."""

    def __init__(self) -> None:
        self._feats = _detect()

    def __getitem__(self, name: str) -> Feature:
        return self._feats[name]

    def __contains__(self, name: str) -> bool:
        return name in self._feats

    def __iter__(self) -> Iterator[str]:
        return iter(self._feats)

    def keys(self):
        return self._feats.keys()

    def values(self):
        return self._feats.values()

    def items(self):
        return self._feats.items()

    def is_enabled(self, name: str) -> bool:
        """True if the named feature is available
        (reference: ``Features.is_enabled``)."""
        return name in self._feats and self._feats[name].enabled

    def __repr__(self) -> str:
        return " ".join(repr(f) for f in self._feats.values())


def device_info() -> dict:
    """The device this process computes on, as jax reports it —
    ``{"platform", "kind", "count"}`` of the default backend.  Servers
    and smokes print it so a process that came up on the CPU is visible
    from outside."""
    import jax
    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def feature_list() -> list:
    """List of all runtime features (reference: ``mx.runtime.feature_list``)."""
    return list(Features().values())
