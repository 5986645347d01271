"""Crash-safe persistent compile cache — compiled XLA executables as
durable, verified artifacts that survive restarts.

PRs 7-8 made process death routine (replica supervisors, elastic PS,
rank restarts), but every restarted worker or serving replica still
re-traced and re-compiled every executable from scratch: recovery was
survivable but slow, and a restart storm multiplies warmup cost across
the fleet.  Following the Julia->TPU full-compilation direction
(PAPERS.md) — a training step / serving bucket is ONE ahead-of-time
compiled program — this module makes those programs durable the same
way PR-3 made checkpoints durable:

* entries are serialized AOT executables
  (``jax.jit(...).lower(...).compile()`` ->
  ``jax.experimental.serialize_executable``), written with the shared
  :mod:`mxnet_tpu._durable` recipe (same-directory staging + fsync +
  atomic rename + SHA-256 manifest + orphan-staging sweep);
* the key covers the **program signature** (SHA-256 of the lowered
  StableHLO module) and the **whole toolchain fingerprint**
  (jax/jaxlib/XLA platform version, backend platform + device kind +
  topology, library version) — a restart on a different toolchain or
  mesh is a clean miss, never a wrong executable;
* corrupted, truncated, or version-mismatched entries are
  **quarantined** (renamed aside, counted in
  ``mxnet_compile_cache_corrupt_total``) and silently recompiled —
  cache failure can NEVER fail a step or a request;
* concurrent multi-process access is safe with **no locks on the read
  path**: readers see either a complete entry or a miss (atomic
  rename; the manifest written last is the commit point), and
  concurrent writers of the same key both stage privately — the last
  rename wins wholesale (single-writer dedupe);
* total size is bounded (``MXNET_COMPILE_CACHE_MAX_BYTES``) with
  oldest-first LRU eviction (mtime refreshed on every hit) that never
  evicts entries **pinned** by live servers (the serving surfaces pin
  their bucket-grid programs; pins are mirrored as on-disk marker
  files so a COOPERATING process — e.g. a trainer sharing the
  directory — honors another process's live grid too).

Compile surfaces wired through :class:`PersistentlyCached` (each falls
back to its plain ``jax.jit`` path on ANY cache trouble):

* ``bulk`` — fused eager-op segment executables (non-recorded
  segments; a recorded segment's vjp closure is not serializable);
* ``spmd.step`` / ``spmd.multi`` — the SPMDTrainer compiled train
  step and the K-step fused program;
* ``serving.export`` / ``serving.decode`` / ``serving.kv`` — the
  one-shot bucket grid, the continuous-batching prefill/decode
  programs, and the KV-cache row-write/grow helpers.

Chaos: ``compile_cache.read`` / ``compile_cache.write`` fault sites
(docs/fault_tolerance.md) prove the degrade-to-recompile path under
``tools/cache_smoke.py``.

Enable by setting ``MXNET_COMPILE_CACHE_DIR`` (every cooperating
process — workers, serving replicas, their supervised restarts — points
at the same directory); ``MXNET_COMPILE_CACHE_DISABLE=1`` is the
kill-switch that wins over a set directory.
"""
from __future__ import annotations

import json
import os
import pickle
import threading
import time
from collections import OrderedDict
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

from .base import getenv, register_env
from . import metrics as _metrics
from . import faults as _faults
from ._durable import (ORPHAN_MIN_AGE_S, sha256_bytes, sweep_orphans,
                       write_bytes_durable)

__all__ = ["CompileCache", "PersistentlyCached", "default_cache",
           "persistently_cached", "cache_stats", "reset_default_cache"]

register_env(
    "MXNET_COMPILE_CACHE_DIR", "",
    "Directory of the crash-safe persistent compile cache: compiled "
    "XLA executables (train steps, serving bucket grids, fused eager "
    "segments) are serialized here with checkpoint-grade durability "
    "and reloaded by restarted processes, so a supervisor- or "
    "launch-restarted worker/replica rejoins with zero steady-state "
    "recompiles. Empty (default) disables persistence. Point every "
    "cooperating process at the same directory.")
register_env(
    "MXNET_COMPILE_CACHE_MAX_BYTES", 2 << 30,
    "Size bound of the persistent compile cache directory; exceeding "
    "it evicts the least-recently-used entries (mtime refreshed on "
    "every hit) that no live server has pinned. 0 disables eviction.")
register_env(
    "MXNET_COMPILE_CACHE_DISABLE", 0,
    "Kill-switch for the persistent compile cache: 1 disables reads "
    "AND writes even when MXNET_COMPILE_CACHE_DIR is set (every "
    "surface falls back to its in-memory jax.jit path).")

CACHE_HITS = _metrics.counter(
    "mxnet_compile_cache_hits_total",
    "Persistent compile-cache lookups that loaded a verified "
    "serialized executable instead of compiling, by surface.",
    labels=("surface",))
CACHE_MISSES = _metrics.counter(
    "mxnet_compile_cache_misses_total",
    "Persistent compile-cache lookups that found no usable entry and "
    "compiled (then wrote back), by surface. A restarted process in "
    "steady state should report 0.", labels=("surface",))
CACHE_WRITES = _metrics.counter(
    "mxnet_compile_cache_writes_total",
    "Entries durably written to the persistent compile cache (staged "
    "+ fsynced + renamed + manifest), by surface.", labels=("surface",))
CACHE_CORRUPT = _metrics.counter(
    "mxnet_compile_cache_corrupt_total",
    "Persistent compile-cache entries quarantined as unusable, by "
    "reason: manifest (unreadable/garbled manifest), missing (payload "
    "gone), digest (SHA-256 mismatch: truncated or bit-flipped), "
    "version (toolchain fingerprint drift under the same key), "
    "deserialize (payload unpickles/loads poisonously). Every one is "
    "silently recompiled.", labels=("reason",))
CACHE_EVICTIONS = _metrics.counter(
    "mxnet_compile_cache_evictions_total",
    "Persistent compile-cache entries removed by LRU size eviction "
    "(pinned entries are never evicted).")
CACHE_BYTES = _metrics.gauge(
    "mxnet_compile_cache_bytes",
    "Bytes held by the persistent compile cache (payloads + "
    "manifests), as of this process's last scan.")
CACHE_ENTRIES = _metrics.gauge(
    "mxnet_compile_cache_entries",
    "Complete entries in the persistent compile cache, as of this "
    "process's last scan.")

_ENTRY_PREFIX = "cc-"
_STAGING_PREFIX = "cc-staging-"
_QUARANTINE_PREFIX = "quarantine-"
_PIN_PREFIX = "ccpin-"

# A pin marker younger than this marks its entry as held by a live
# server SOMEWHERE in the fleet (pin sets are process memory; markers
# make them visible to every cooperating evictor).  Markers are
# refreshed on pin and on every load of their entry; older ones are
# presumed to belong to dead processes and are swept at init.
PIN_TTL_S = 86400.0

_FP_LOCK = threading.Lock()
_FP: Dict[str, str] = {}


def _fingerprint() -> Dict[str, str]:
    """The toolchain/topology identity baked into every key AND
    double-checked against the manifest on load (defense in depth for
    a hash collision or a hand-edited manifest)."""
    with _FP_LOCK:
        if _FP:
            return dict(_FP)
        import jax
        import jaxlib
        try:
            backend = jax.devices()[0].client
            platform = str(getattr(backend, "platform", "?"))
            platform_version = str(getattr(backend, "platform_version",
                                           "?"))
            device_kind = str(jax.devices()[0].device_kind)
        except Exception:   # noqa: BLE001 - no backend: fingerprint
            platform = platform_version = device_kind = "?"
        import mxnet_tpu
        _FP.update({
            "jax": jax.__version__,
            "jaxlib": jaxlib.__version__,
            "platform": platform,
            "platform_version": platform_version,
            "device_kind": device_kind,
            "devices": str(jax.device_count()),
            "processes": str(jax.process_count()),
            "library": getattr(mxnet_tpu, "__version__", "?"),
        })
        return dict(_FP)


def _sig_of(args: Tuple[Any, ...]) -> Tuple[Any, Any]:
    """Hashable input-signature of a call: pytree structure + per-leaf
    (shape, dtype, weak_type, sharding).  Shardings participate because
    the same avals under a different placement lower to a different
    program."""
    import jax
    leaves, treedef = jax.tree_util.tree_flatten(args)
    sig: List[Any] = []
    for leaf in leaves:
        if hasattr(leaf, "shape") and hasattr(leaf, "dtype"):
            aval = getattr(leaf, "aval", None)
            sig.append((tuple(leaf.shape), str(leaf.dtype),
                        bool(getattr(aval, "weak_type", False)),
                        getattr(leaf, "sharding", None)))
        else:
            # python scalars trace as weak-typed value-independent
            # avals: one memo entry covers every value
            sig.append(("py", type(leaf).__name__))
    return treedef, tuple(sig)


class CompileCache:
    """One cache directory: verified load, durable store, LRU+pin
    eviction.  All methods are safe to call from any thread and any
    number of cooperating processes."""

    def __init__(self, directory: str,
                 max_bytes: Optional[int] = None) -> None:
        self.directory = directory
        self.max_bytes = int(
            max_bytes if max_bytes is not None
            else getenv("MXNET_COMPILE_CACHE_MAX_BYTES", 2 << 30))
        os.makedirs(directory, exist_ok=True)
        # crash debris from dead writers (staged payloads) and old
        # quarantined entries; age-guarded so live writers survive
        sweep_orphans(directory, (_STAGING_PREFIX, _QUARANTINE_PREFIX))
        # pin markers from long-dead servers (a live server's markers
        # stay fresh: loads and the wrapper's periodic refresh re-touch
        # them)
        sweep_orphans(directory, (_PIN_PREFIX,), min_age_s=PIN_TTL_S)
        # payloads whose manifest never landed (crash between store()'s
        # two durable writes): invisible to readers AND to the size
        # accounting, so reclaim them here — age-guarded, a live
        # writer's rename-to-rename window is milliseconds
        self._sweep_unreferenced()
        self._pinned: set = set()
        self._lock = threading.Lock()
        self._store_broken = False
        self._update_gauges()

    # -- keys ----------------------------------------------------------
    def key_for(self, lowered: Any, extra: Sequence[Any] = ()) -> str:
        """SHA-256 over (toolchain fingerprint, lowered StableHLO
        module text, caller extras) — the full version key."""
        import hashlib
        h = hashlib.sha256()
        fp = _fingerprint()
        for k in sorted(fp):
            h.update(f"{k}={fp[k]}\n".encode())
        h.update(lowered.as_text().encode())
        for e in extra:
            h.update(repr(e).encode())
        return h.hexdigest()

    def _exe_path(self, key: str) -> str:
        return os.path.join(self.directory, f"{_ENTRY_PREFIX}{key}.exe")

    def _man_path(self, key: str) -> str:
        return os.path.join(self.directory, f"{_ENTRY_PREFIX}{key}.json")

    def _pin_path(self, key: str) -> str:
        return os.path.join(self.directory, f"{_PIN_PREFIX}{key}")

    # -- pinning -------------------------------------------------------
    def pin(self, key: str) -> None:
        """Mark ``key`` as held by a live server: eviction will never
        remove it — not this process's eviction (the in-memory set) and
        not a cooperating process's (the on-disk marker)."""
        with self._lock:
            self._pinned.add(key)
        path = self._pin_path(key)
        try:
            with open(path, "a"):
                pass
            os.utime(path, None)
        except OSError:
            pass    # marker failed: the pin stays process-local

    def pinned(self) -> set:
        with self._lock:
            return set(self._pinned)

    def _disk_pins(self) -> set:
        """Keys pinned by ANY cooperating process: fresh-mtime markers
        (a dead server's markers age out past PIN_TTL_S)."""
        out: set = set()
        try:
            names = os.listdir(self.directory)
        except OSError:
            return out
        now = time.time()
        for name in names:
            if not name.startswith(_PIN_PREFIX):
                continue
            try:
                mtime = os.path.getmtime(
                    os.path.join(self.directory, name))
            except OSError:
                continue
            if now - mtime <= PIN_TTL_S:
                out.add(name[len(_PIN_PREFIX):])
        return out

    # -- read path (lock-free) -----------------------------------------
    def load(self, key: str, surface: str = "unknown") -> Optional[Any]:
        """A loaded, callable executable for ``key``, or None (miss).
        Any unusable entry is quarantined and reported as a miss —
        this method never raises for cache reasons."""
        try:
            _faults.maybe_fault("compile_cache.read", key=key[:12],
                                surface=surface)
        except Exception:   # noqa: BLE001 - injected read failure:
            return None     # degrade to a miss (recompile), by design
        man, exe = self._man_path(key), self._exe_path(key)
        try:
            with open(man, "r") as f:
                meta = json.load(f)
        except FileNotFoundError:
            return None                          # clean miss
        except Exception:   # noqa: BLE001 - unreadable/garbled manifest
            self._quarantine(key, "manifest")
            return None
        if meta.get("fingerprint") != _fingerprint():
            self._quarantine(key, "version")
            return None
        try:
            with open(exe, "rb") as f:
                blob = f.read()
        except OSError:
            self._quarantine(key, "missing")
            return None
        if sha256_bytes(blob) != meta.get("sha256"):
            self._quarantine(key, "digest")
            return None
        try:
            import jax
            from jax.experimental import serialize_executable as _se
            payload, in_tree, out_tree = pickle.loads(blob)
            # load onto the devices the program was compiled for:
            # deserialize_and_load otherwise targets EVERY backend
            # device, and a 1-device program then expects N shards
            by_id = {d.id: d for d in jax.devices()}
            fn = _se.deserialize_and_load(
                payload, in_tree, out_tree,
                execution_devices=[by_id[i] for i in meta["devices"]])
        except Exception:   # noqa: BLE001 - verified bytes that still
            self._quarantine(key, "deserialize")  # refuse to load
            return None
        # LRU recency for the shared evictor (best effort: another
        # process may be evicting this very entry — still a valid
        # load); an existing pin marker is refreshed too, so a live
        # server's grid never ages past PIN_TTL_S while in use
        for path in (exe, man, self._pin_path(key)):
            try:
                os.utime(path, None)
            except OSError:
                pass
        return fn

    def _quarantine(self, key: str, reason: str) -> None:
        """Move a poisoned entry aside so the next lookup is a clean
        miss (recompile + overwrite) instead of re-reading poison every
        step.  Quarantined files are reclaimed by the init sweep."""
        CACHE_CORRUPT.labels(reason=reason).inc()
        stamp = f"{_QUARANTINE_PREFIX}{reason}-{_ENTRY_PREFIX}{key}"
        for src, suffix in ((self._exe_path(key), ".exe"),
                            (self._man_path(key), ".json")):
            try:
                os.replace(src, os.path.join(self.directory,
                                             stamp + suffix))
            except OSError:
                pass    # already quarantined/evicted by someone else
        self._update_gauges()

    # -- write path ----------------------------------------------------
    def store(self, key: str, compiled: Any,
              surface: str = "unknown") -> bool:
        """Durably persist ``compiled`` under ``key``; returns True on
        a completed (or already-present) entry.  Never raises for
        cache reasons."""
        if self._store_broken:
            return False
        man, exe = self._man_path(key), self._exe_path(key)
        if os.path.exists(man) and os.path.exists(exe):
            return True     # another writer won the rename: dedupe
        try:
            _faults.maybe_fault("compile_cache.write", key=key[:12],
                                surface=surface)
        except Exception:   # noqa: BLE001 - ANY injected write fault
            # (error/timeout/...) abandons THIS write only — the next
            # program still persists
            return False
        try:
            from jax.experimental import serialize_executable as _se
            payload, in_tree, out_tree = _se.serialize(compiled)
            blob = pickle.dumps((payload, in_tree, out_tree),
                                protocol=pickle.HIGHEST_PROTOCOL)
        except Exception:   # noqa: BLE001 - backend cannot serialize
            # (or the out-tree holds unpicklable closures): stop paying
            # the serialization attempt per program
            self._store_broken = True
            return False
        try:
            # payload first, manifest last: the manifest is the commit
            # point a reader requires, so a crash between the two
            # renames leaves an invisible (unreferenced) payload the
            # next writer simply overwrites
            digest = write_bytes_durable(exe, blob, _STAGING_PREFIX)
            meta = {
                "key": key,
                "sha256": digest,
                "size": len(blob),
                "surface": surface,
                "devices": [d.id for d in
                            compiled.runtime_executable().local_devices()],
                "fingerprint": _fingerprint(),
                "created": time.time(),
            }
            write_bytes_durable(
                man, json.dumps(meta, sort_keys=True).encode(),
                _STAGING_PREFIX)
        except Exception:   # noqa: BLE001 - disk full / perms: degrade
            return False
        CACHE_WRITES.labels(surface=surface).inc()
        # a write never evicts itself: under a budget tighter than one
        # entry the freshly persisted program must still survive long
        # enough for its own process's restart to matter
        self._evict_if_needed(keep={key})
        return True

    def _sweep_unreferenced(self) -> None:
        """Remove aged cc-*.exe payloads with no manifest — crash
        debris a reader can never see and ``_entries`` never counts."""
        try:
            names = os.listdir(self.directory)
        except OSError:
            return
        now = time.time()
        for name in names:
            if not (name.startswith(_ENTRY_PREFIX)
                    and name.endswith(".exe")):
                continue
            key = name[len(_ENTRY_PREFIX):-len(".exe")]
            if os.path.exists(self._man_path(key)):
                continue
            path = os.path.join(self.directory, name)
            try:
                if now - os.path.getmtime(path) < ORPHAN_MIN_AGE_S:
                    continue
                os.remove(path)
            except OSError:
                pass

    # -- size bound ----------------------------------------------------
    def _entries(self) -> List[Tuple[str, float, int]]:
        """(key, mtime, bytes) per COMPLETE entry (manifest present)."""
        out = []
        try:
            names = os.listdir(self.directory)
        except OSError:
            return out
        for name in names:
            if not (name.startswith(_ENTRY_PREFIX)
                    and name.endswith(".json")):
                continue
            key = name[len(_ENTRY_PREFIX):-len(".json")]
            size = 0
            mtime = 0.0
            try:
                for path in (self._man_path(key), self._exe_path(key)):
                    st = os.stat(path)
                    size += st.st_size
                    mtime = max(mtime, st.st_mtime)
            except OSError:
                continue        # half-evicted by a peer: skip
            out.append((key, mtime, size))
        return out

    def _update_gauges(self,
                       entries: Optional[List[Tuple[str, float, int]]]
                       = None) -> None:
        if entries is None:
            entries = self._entries()
        CACHE_ENTRIES.set(len(entries))
        CACHE_BYTES.set(sum(e[2] for e in entries))

    def _evict_if_needed(self, keep: Optional[set] = None) -> int:
        """Oldest-first LRU eviction down to ``max_bytes``; pinned
        entries (and ``keep``) survive regardless.  Returns entries
        evicted."""
        if self.max_bytes <= 0:
            self._update_gauges()
            return 0
        entries = self._entries()
        total = sum(e[2] for e in entries)
        if total <= self.max_bytes:
            self._update_gauges(entries)
            return 0
        pinned = self.pinned() | self._disk_pins() | (keep or set())
        evicted = 0
        for key, _mtime, size in sorted(entries, key=lambda e: e[1]):
            if total <= self.max_bytes:
                break
            if key in pinned:
                continue
            # manifest first: readers see a clean miss, never a
            # manifest-without-payload corruption event; any stale pin
            # marker goes with the entry
            for path in (self._man_path(key), self._exe_path(key),
                         self._pin_path(key)):
                try:
                    os.remove(path)
                except OSError:
                    pass
            total -= size
            evicted += 1
            CACHE_EVICTIONS.inc()
        self._update_gauges()
        return evicted

    def stats(self) -> Dict[str, Any]:
        entries = self._entries()
        return {
            "directory": self.directory,
            "entries": len(entries),
            "bytes": sum(e[2] for e in entries),
            "max_bytes": self.max_bytes,
            "pinned": len(self.pinned() | self._disk_pins()),
        }


# ---------------------------------------------------------------------------
# The process-default cache (env-configured)
# ---------------------------------------------------------------------------

_DEFAULT_LOCK = threading.Lock()
_DEFAULT: Dict[str, Any] = {"env": None, "cache": None, "gen": 0}


def default_cache() -> Optional[CompileCache]:
    """The env-configured cache, or None when disabled.  Re-reads the
    env tier on every call (cheap), so tests and tools can point a
    process at a directory without import-order gymnastics."""
    d = str(getenv("MXNET_COMPILE_CACHE_DIR", "") or "")
    dis = str(getenv("MXNET_COMPILE_CACHE_DISABLE", 0))
    mb = str(getenv("MXNET_COMPILE_CACHE_MAX_BYTES", 2 << 30))
    env = (d, dis, mb)
    if _DEFAULT["env"] == env:
        return _DEFAULT["cache"]
    with _DEFAULT_LOCK:
        if _DEFAULT["env"] == env:
            return _DEFAULT["cache"]
        cache = None
        if d and dis.strip().lower() not in ("1", "true", "yes"):
            try:
                cache = CompileCache(d, max_bytes=int(float(mb)))
            except Exception:   # noqa: BLE001 - unusable dir: disabled
                cache = None
        _DEFAULT["env"] = env
        _DEFAULT["cache"] = cache
        # a changed env invalidates every wrapper's latched resolution
        # too — the first default_cache() call that notices the change
        # (a new wrapper, cache_stats, /v1/model) propagates it
        _DEFAULT["gen"] += 1
    return cache


def reset_default_cache() -> None:
    """Forget the memoized default cache and invalidate every
    :class:`PersistentlyCached` wrapper's latched resolution (the
    wrappers re-read the env on their next call).  Call after changing
    the ``MXNET_COMPILE_CACHE_*`` env mid-process (tests, tools); this
    also drops the in-process pin set."""
    with _DEFAULT_LOCK:
        _DEFAULT["env"] = None
        _DEFAULT["cache"] = None
        _DEFAULT["gen"] += 1


def _family_total(family: Any) -> float:
    return sum(child.value for _vals, child in family._series())


def cache_stats() -> Dict[str, Any]:
    """Stats of the default cache ({} when disabled) — serving /v1/model
    and tools surface this.  Counter totals are THIS process's
    (directory-level entries/bytes are shared)."""
    cache = default_cache()
    if cache is None:
        return {}
    s = cache.stats()
    s.update(
        hits=_family_total(CACHE_HITS),
        misses=_family_total(CACHE_MISSES),
        writes=_family_total(CACHE_WRITES),
        corrupt=_family_total(CACHE_CORRUPT),
        evictions=CACHE_EVICTIONS.value,
    )
    return s


# ---------------------------------------------------------------------------
# PersistentlyCached — the surface wrapper
# ---------------------------------------------------------------------------

class PersistentlyCached:
    """Wrap a ``jax.jit``-wrapped callable with per-input-signature AOT
    compilation through the persistent cache.

    First call at a signature: lower (trace only), derive the version
    key, try the cache — a verified hit loads the serialized executable
    (zero XLA compile), a miss compiles AOT and durably writes back.
    Later calls dispatch the memoized executable directly.  With no
    cache configured, or on ANY cache/AOT trouble, the call degrades to
    the wrapped ``jax.jit`` path — bit-identical semantics, never a new
    failure mode.
    """

    _MEMO_CAP = 64
    # pinned wrappers re-touch their on-disk markers at this cadence
    # (steady-state traffic hits the memo, never load()/pin(), so
    # without it a busy server's markers would age past PIN_TTL_S and
    # lose eviction protection against cooperating processes)
    _PIN_REFRESH_S = PIN_TTL_S / 8.0

    __slots__ = ("_jitted", "_surface", "_extra", "_pin", "_memo",
                 "_lock", "_cache", "_cache_gen", "_pin_keys",
                 "_pin_refresh_t")

    def __init__(self, jitted: Callable, surface: str,
                 extra_key: Sequence[Any] = (),
                 pin: bool = False) -> None:
        self._jitted = jitted
        self._surface = surface
        self._extra = tuple(extra_key)
        self._pin = bool(pin)
        self._memo: "OrderedDict[Any, Callable]" = OrderedDict()
        self._lock = threading.Lock()
        self._cache: Optional[CompileCache] = None
        self._cache_gen = -1        # unresolved: first call latches
        self._pin_keys: List[str] = []
        self._pin_refresh_t = time.monotonic()

    def lower(self, *args: Any, **kwargs: Any) -> Any:
        """Delegate AOT inspection to the wrapped ``jax.jit`` (tests
        and tools lower the step to read its StableHLO)."""
        return self._jitted.lower(*args, **kwargs)

    def __call__(self, *args: Any) -> Any:
        # the env resolution is latched per wrapper (reset_default_cache
        # invalidates): the disabled case — most processes — costs one
        # int compare per call, not three env reads
        if self._cache_gen != _DEFAULT["gen"]:
            self._cache = default_cache()
            self._cache_gen = _DEFAULT["gen"]
        cache = self._cache
        if cache is None:
            return self._jitted(*args)
        try:
            sig = _sig_of(args)
        except Exception:   # noqa: BLE001 - unhashable exotic leaf
            return self._jitted(*args)
        with self._lock:
            fn = self._memo.get(sig)
            if fn is not None:
                self._memo.move_to_end(sig)
        if self._pin and self._pin_keys and \
                time.monotonic() - self._pin_refresh_t \
                > self._PIN_REFRESH_S:
            self._refresh_pins(cache)
        if fn is None:
            fn = self._acquire(cache, args)
            with self._lock:
                self._memo[sig] = fn
                if len(self._memo) > self._MEMO_CAP:
                    self._memo.popitem(last=False)
        if fn is self._jitted:
            return fn(*args)
        try:
            return fn(*args)
        except Exception:   # noqa: BLE001
            # a loaded executable rejected these args (e.g. placement
            # drift the signature missed): degrade this signature to
            # the jit path — unless the executable already consumed
            # donated inputs, where a retry would read deleted buffers
            # (then the original error IS the truthful one)
            import jax
            for leaf in jax.tree_util.tree_leaves(args):
                if getattr(leaf, "is_deleted", None) is not None \
                        and leaf.is_deleted():
                    raise
            with self._lock:
                self._memo[sig] = self._jitted
            return self._jitted(*args)

    def _refresh_pins(self, cache: CompileCache) -> None:
        """Re-touch this wrapper's pin markers so a busy server's grid
        never ages out of the fleet-wide eviction protection."""
        with self._lock:
            if time.monotonic() - self._pin_refresh_t \
                    <= self._PIN_REFRESH_S:
                return              # another thread just did it
            self._pin_refresh_t = time.monotonic()
            keys = list(self._pin_keys)
        for key in keys:
            cache.pin(key)

    def _acquire(self, cache: CompileCache,
                 args: Tuple[Any, ...]) -> Callable:
        try:
            lowered = self._jitted.lower(*args)
            key = cache.key_for(lowered, self._extra)
        except Exception:   # noqa: BLE001 - a lower failure is a real
            # trace problem: the jit path will surface it faithfully
            return self._jitted
        fn = cache.load(key, surface=self._surface)
        if fn is not None:
            CACHE_HITS.labels(surface=self._surface).inc()
            if self._pin:
                self._remember_pin(cache, key)
            return fn
        CACHE_MISSES.labels(surface=self._surface).inc()
        try:
            compiled = lowered.compile()
        except Exception:   # noqa: BLE001 - real compile error: let
            return self._jitted     # the jit path raise it
        if self._pin:
            self._remember_pin(cache, key)  # before store: its own
            #                     eviction pass must already see the pin
        cache.store(key, compiled, surface=self._surface)
        return compiled

    def _remember_pin(self, cache: CompileCache, key: str) -> None:
        cache.pin(key)
        with self._lock:
            if key not in self._pin_keys:
                self._pin_keys.append(key)


def persistently_cached(jitted: Callable, surface: str,
                        extra_key: Sequence[Any] = (),
                        pin: bool = False) -> PersistentlyCached:
    """Convenience constructor (the call sites read better)."""
    return PersistentlyCached(jitted, surface, extra_key=extra_key,
                              pin=pin)
