"""Persistent compile cache (mxnet_tpu/compile_cache.py): version-keyed
hits/misses, corruption quarantine, concurrent write dedupe, LRU+pin
eviction, fault-site determinism, and the kill-and-restart subprocess
proof (0 steady-state compiles, loss parity).

The tier-1 warm-restart gate lives in ``ci/run.sh cache-smoke``
(tools/cache_smoke.py); these tests pin the cache's component
contracts."""
import glob
import json
import os
import pickle
import subprocess
import sys
import textwrap
import time

import numpy as onp
import pytest

import jax
import jax.numpy as jnp

from mxnet_tpu import compile_cache as cc
from mxnet_tpu import faults


def _make(scale: float):
    """A distinct tiny program per ``scale`` (the constant embeds in
    the lowered module, so each scale is its own cache key)."""
    return jax.jit(lambda x, _s=float(scale): x * _s + 1.0)


X = jnp.ones((8, 8), jnp.float32)


def _fill(cache: cc.CompileCache, scale: float,
          surface: str = "test") -> str:
    """Compile + store one program; returns its key."""
    jitted = _make(scale)
    lowered = jitted.lower(X)
    key = cache.key_for(lowered)
    assert cache.store(key, lowered.compile(), surface=surface)
    return key


# ---------------------------------------------------------------------------
# hit / miss / version-key matrix
# ---------------------------------------------------------------------------

def test_hit_miss_and_write(tmp_path):
    cache = cc.CompileCache(str(tmp_path))
    jitted = _make(2.0)
    lowered = jitted.lower(X)
    key = cache.key_for(lowered)
    assert cache.load(key) is None                  # clean miss
    assert cache.store(key, lowered.compile(), surface="test")
    fn = cache.load(key)                            # verified hit
    assert fn is not None
    onp.testing.assert_array_equal(onp.asarray(fn(X)),
                                   onp.asarray(jitted(X)))
    # storing again dedupes on the existing complete entry
    assert cache.store(key, lowered.compile(), surface="test")
    assert cache.stats()["entries"] == 1


def test_version_key_matrix(tmp_path, monkeypatch):
    cache = cc.CompileCache(str(tmp_path))
    base = cache.key_for(_make(2.0).lower(X))
    # same program, same toolchain -> same key (restart determinism)
    assert cache.key_for(_make(2.0).lower(X)) == base
    # different program -> different key
    assert cache.key_for(_make(3.0).lower(X)) != base
    # same program, different input aval -> different key
    assert cache.key_for(
        _make(2.0).lower(jnp.ones((4, 8), jnp.float32))) != base
    # caller extras participate
    assert cache.key_for(_make(2.0).lower(X), extra=("v2",)) != base
    # any toolchain fingerprint drift changes the key
    cc._fingerprint()                               # populate the memo
    monkeypatch.setitem(cc._FP, "library", "someone-elses-build")
    assert cache.key_for(_make(2.0).lower(X)) != base


def test_version_mismatch_quarantines(tmp_path):
    cache = cc.CompileCache(str(tmp_path))
    key = _fill(cache, 2.0)
    # a manifest whose recorded fingerprint drifted from this process
    # (hash collision / hand-edited entry): quarantined, not loaded
    man = cache._man_path(key)
    with open(man) as f:
        meta = json.load(f)
    meta["fingerprint"]["jax"] = "0.0.1"
    with open(man, "w") as f:
        json.dump(meta, f)
    before = cc._family_total(cc.CACHE_CORRUPT)
    assert cache.load(key) is None
    assert cc._family_total(cc.CACHE_CORRUPT) == before + 1
    assert cache.load(key) is None                  # now a clean miss
    assert cc._family_total(cc.CACHE_CORRUPT) == before + 1


# ---------------------------------------------------------------------------
# corruption -> quarantine -> recompile
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("poison", ["truncate", "bitflip", "manifest",
                                    "missing"])
def test_corruption_quarantines_and_recovers(tmp_path, poison):
    cache = cc.CompileCache(str(tmp_path))
    key = _fill(cache, 5.0)
    exe, man = cache._exe_path(key), cache._man_path(key)
    if poison == "truncate":
        with open(exe, "r+b") as f:
            f.truncate(10)
    elif poison == "bitflip":
        with open(exe, "r+b") as f:
            blob = bytearray(f.read())
            blob[len(blob) // 2] ^= 0xFF
            f.seek(0)
            f.write(blob)
    elif poison == "manifest":
        with open(man, "w") as f:
            f.write("{ definitely not json")
    else:
        os.remove(exe)
    before = cc._family_total(cc.CACHE_CORRUPT)
    assert cache.load(key) is None                  # degrade, no raise
    assert cc._family_total(cc.CACHE_CORRUPT) == before + 1
    assert glob.glob(str(tmp_path / "quarantine-*"))
    # the slot is clean again: a recompile overwrites it and serves
    assert _fill(cache, 5.0) == key
    assert cache.load(key) is not None


def test_unpicklable_payload_quarantines(tmp_path):
    cache = cc.CompileCache(str(tmp_path))
    key = _fill(cache, 6.0)
    # valid manifest + digest over bytes that are not an executable at
    # all: the deserialize stage must quarantine, never raise
    from mxnet_tpu._durable import write_bytes_durable
    blob = pickle.dumps({"not": "an executable"})
    digest = write_bytes_durable(cache._exe_path(key), blob)
    man = cache._man_path(key)
    with open(man) as f:
        meta = json.load(f)
    meta["sha256"] = digest
    with open(man, "w") as f:
        json.dump(meta, f)
    before = cc._family_total(cc.CACHE_CORRUPT)
    assert cache.load(key) is None
    assert cc._family_total(cc.CACHE_CORRUPT) == before + 1


# ---------------------------------------------------------------------------
# PersistentlyCached wrapper semantics
# ---------------------------------------------------------------------------

def test_wrapper_miss_then_cross_instance_hit(tmp_path, monkeypatch):
    monkeypatch.setenv("MXNET_COMPILE_CACHE_DIR", str(tmp_path))
    cc.reset_default_cache()
    h0 = cc._family_total(cc.CACHE_HITS)
    m0 = cc._family_total(cc.CACHE_MISSES)
    a = cc.persistently_cached(_make(7.0), "test")
    out1 = a(X)
    assert cc._family_total(cc.CACHE_MISSES) == m0 + 1
    out1b = a(X)                    # memoized: no new counters
    assert cc._family_total(cc.CACHE_MISSES) == m0 + 1
    # a fresh wrapper (= a restarted process's view) hits the disk
    b = cc.persistently_cached(_make(7.0), "test")
    out2 = b(X)
    assert cc._family_total(cc.CACHE_HITS) == h0 + 1
    onp.testing.assert_array_equal(onp.asarray(out1), onp.asarray(out2))
    onp.testing.assert_array_equal(onp.asarray(out1),
                                   onp.asarray(out1b))
    cc.reset_default_cache()


def test_wrapper_disabled_paths(tmp_path, monkeypatch):
    # no dir -> plain jit path, zero cache traffic
    monkeypatch.delenv("MXNET_COMPILE_CACHE_DIR", raising=False)
    cc.reset_default_cache()
    assert cc.default_cache() is None
    w0 = cc._family_total(cc.CACHE_WRITES)
    fn = cc.persistently_cached(_make(8.0), "test")
    fn(X)
    assert cc._family_total(cc.CACHE_WRITES) == w0
    # the kill-switch wins over a set dir
    monkeypatch.setenv("MXNET_COMPILE_CACHE_DIR", str(tmp_path))
    monkeypatch.setenv("MXNET_COMPILE_CACHE_DISABLE", "1")
    assert cc.default_cache() is None
    assert cc.cache_stats() == {}
    cc.reset_default_cache()


# ---------------------------------------------------------------------------
# LRU eviction bounds + pinning
# ---------------------------------------------------------------------------

def test_lru_eviction_bounds_and_pins(tmp_path):
    cache = cc.CompileCache(str(tmp_path), max_bytes=1)  # evict hard
    e0 = cc.CACHE_EVICTIONS.value
    k_pinned = _fill(cache, 10.0)
    cache.pin(k_pinned)
    keys = [_fill(cache, 10.0 + i) for i in range(1, 5)]
    # under a budget tighter than one entry, only the pinned entry and
    # the most recent write survive (a write never evicts itself);
    # every other entry was evicted oldest-first on the way
    assert cache.load(k_pinned) is not None
    assert cc.CACHE_EVICTIONS.value - e0 == len(keys) - 1
    stats = cache.stats()
    assert stats["entries"] == 2 and stats["pinned"] == 1
    for k in keys[:-1]:
        assert not os.path.exists(cache._man_path(k))
    assert os.path.exists(cache._man_path(keys[-1]))

    # a generous budget keeps everything
    roomy = cc.CompileCache(str(tmp_path / "roomy"), max_bytes=1 << 30)
    for i in range(3):
        _fill(roomy, 20.0 + i)
    assert roomy.stats()["entries"] == 3


def test_pin_survives_other_process_eviction(tmp_path):
    """Pins are mirrored on disk: an evictor in a DIFFERENT process
    (here: a second CompileCache over the same directory, with an empty
    in-memory pin set) must honor a live server's pinned grid."""
    server = cc.CompileCache(str(tmp_path), max_bytes=1 << 30)
    k_grid = _fill(server, 50.0)
    server.pin(k_grid)
    os.utime(server._exe_path(k_grid), (1, 1))      # oldest entry
    os.utime(server._man_path(k_grid), (1, 1))
    trainer = cc.CompileCache(str(tmp_path), max_bytes=1 << 30)
    for i in range(1, 4):
        _fill(trainer, 50.0 + i)
    trainer.max_bytes = 1                           # evict hard
    trainer._evict_if_needed()
    assert trainer.pinned() == set()                # no local pin...
    assert server.load(k_grid) is not None          # ...entry survives
    assert trainer.stats()["entries"] >= 1

    # a STALE marker (dead server: aged past PIN_TTL_S) stops pinning
    # and is reclaimed by the next init sweep
    old = time.time() - cc.PIN_TTL_S - 60
    os.utime(server._pin_path(k_grid), (old, old))
    assert k_grid not in trainer._disk_pins()
    cc.CompileCache(str(tmp_path))
    assert not os.path.exists(server._pin_path(k_grid))


def test_wrapper_delegates_lower(tmp_path, monkeypatch):
    """tests/tools lower the wrapped step to inspect its StableHLO —
    the wrapper must expose the jit's AOT surface."""
    monkeypatch.setenv("MXNET_COMPILE_CACHE_DIR", str(tmp_path))
    cc.reset_default_cache()
    fn = cc.persistently_cached(_make(51.0), "test")
    assert "stablehlo" in fn.lower(X).as_text().lower() or \
        "module" in fn.lower(X).as_text()
    cc.reset_default_cache()


def test_lru_prefers_oldest(tmp_path):
    cache = cc.CompileCache(str(tmp_path), max_bytes=1 << 30)
    k1 = _fill(cache, 30.0)
    k2 = _fill(cache, 31.0)
    k3 = _fill(cache, 32.0)
    os.utime(cache._exe_path(k1), (1, 1))       # k1 is coldest
    os.utime(cache._man_path(k1), (1, 1))
    os.utime(cache._exe_path(k2), (2, 2))
    os.utime(cache._man_path(k2), (2, 2))
    entry_bytes = cache.stats()["bytes"] // 3
    cache.max_bytes = entry_bytes * 2 + 64      # room for ~2 entries
    cache._evict_if_needed()
    assert not os.path.exists(cache._man_path(k1))
    assert os.path.exists(cache._man_path(k2))
    assert os.path.exists(cache._man_path(k3))


# ---------------------------------------------------------------------------
# fault sites: degrade to miss / abandoned write, deterministically
# ---------------------------------------------------------------------------

def test_read_fault_degrades_to_miss(tmp_path):
    cache = cc.CompileCache(str(tmp_path))
    key = _fill(cache, 40.0)
    with faults.fault_plan("compile_cache.read:times=2") as fp:
        assert cache.load(key) is None      # injected: miss, no raise
        assert cache.load(key) is None
        assert cache.load(key) is not None  # plan exhausted: hit again
    assert fp.specs[0].injected == 2
    # a healthy entry is NEVER quarantined by an injected read fault
    assert not glob.glob(str(tmp_path / "quarantine-*"))


def test_write_fault_abandons_write(tmp_path):
    cache = cc.CompileCache(str(tmp_path))
    jitted = _make(41.0)
    lowered = jitted.lower(X)
    key = cache.key_for(lowered)
    compiled = lowered.compile()
    with faults.fault_plan("compile_cache.write:times=1"):
        assert not cache.store(key, compiled, surface="test")
    assert cache.load(key) is None          # nothing half-written
    assert not glob.glob(str(tmp_path / "cc-staging-*"))
    assert cache.store(key, compiled, surface="test")   # clean retry


def test_write_fault_kinds_never_disable_the_store(tmp_path):
    """Every injected write-fault kind (error raises MXNetError-family,
    timeout raises socket.timeout) abandons ONE write — none may trip
    the permanent cannot-serialize kill switch."""
    cache = cc.CompileCache(str(tmp_path))
    jitted = _make(43.0)
    lowered = jitted.lower(X)
    key = cache.key_for(lowered)
    compiled = lowered.compile()
    for kind in ("error", "timeout"):
        with faults.fault_plan(f"compile_cache.write:times=1:kind={kind}"):
            assert not cache.store(key, compiled, surface="test")
        assert not cache._store_broken
        assert cache.store(key, compiled, surface="test")
        for p in (cache._man_path(key), cache._exe_path(key)):
            os.remove(p)


def test_env_change_propagates_to_latched_wrappers(tmp_path,
                                                   monkeypatch):
    """A wrapper latched while the cache was disabled must pick up a
    later env change once anything re-resolves the default cache
    (cache_stats / a server's /v1/model does this every scrape)."""
    monkeypatch.delenv("MXNET_COMPILE_CACHE_DIR", raising=False)
    cc.reset_default_cache()
    fn = cc.persistently_cached(_make(44.0), "test")
    fn(X)                                   # latches cache=None
    monkeypatch.setenv("MXNET_COMPILE_CACHE_DIR", str(tmp_path))
    assert cc.default_cache() is not None   # re-resolve bumps the gen
    w0 = cc._family_total(cc.CACHE_WRITES)
    fn(X)                                   # wrapper re-latches
    assert cc._family_total(cc.CACHE_WRITES) == w0 + 1
    cc.reset_default_cache()


def test_unreferenced_payload_swept_at_init(tmp_path):
    cache = cc.CompileCache(str(tmp_path))
    orphan = cache._exe_path("deadbeef")    # store() crashed between
    with open(orphan, "wb") as f:           # the payload and manifest
        f.write(b"x" * 64)                  # renames
    fresh = cache._exe_path("cafef00d")
    with open(fresh, "wb") as f:
        f.write(b"y" * 64)
    old = time.time() - 3600
    os.utime(orphan, (old, old))
    cc.CompileCache(str(tmp_path))
    assert not os.path.exists(orphan)       # aged: reclaimed
    assert os.path.exists(fresh)            # young: a live writer's


def test_pinned_wrapper_refreshes_markers(tmp_path, monkeypatch):
    """A busy server never calls load() after the memo warms — the
    wrapper itself must re-touch its pin markers so they stay fresh."""
    monkeypatch.setenv("MXNET_COMPILE_CACHE_DIR", str(tmp_path))
    cc.reset_default_cache()
    fn = cc.persistently_cached(_make(45.0), "test", pin=True)
    fn(X)
    cache = cc.default_cache()
    (key,) = cache.pinned()
    marker = cache._pin_path(key)
    old = time.time() - cc.PIN_TTL_S - 60
    os.utime(marker, (old, old))            # pretend 24h passed
    # ...for the wrapper clock too.  Relative to monotonic NOW, not an
    # absolute 0.0: time.monotonic() is boot-relative, so on a machine
    # up for less than _PIN_REFRESH_S (3h) a zeroed stamp would read as
    # "recently refreshed" and the wrapper would legitimately skip the
    # re-touch (this test used to fail on freshly booted CI containers)
    fn._pin_refresh_t = time.monotonic() - cc.PIN_TTL_S
    fn(X)                                   # memo hit still refreshes
    assert time.time() - os.path.getmtime(marker) < 60
    assert key in cache._disk_pins()
    cc.reset_default_cache()


def test_fault_schedule_is_deterministic(tmp_path):
    cache = cc.CompileCache(str(tmp_path))
    key = _fill(cache, 42.0)

    def schedule():
        with faults.fault_plan("compile_cache.read:p=0.4:seed=11"):
            return [cache.load(key) is not None for _ in range(16)]

    first = schedule()
    assert first == schedule() == schedule()
    assert True in first and False in first     # p=0.4 actually mixes


# ---------------------------------------------------------------------------
# concurrent two-process write dedupe
# ---------------------------------------------------------------------------

_WRITER = textwrap.dedent("""
    import os, sys, json
    os.environ["JAX_PLATFORMS"] = "cpu"
    sys.path.insert(0, {repo!r})
    import jax, jax.numpy as jnp
    from mxnet_tpu import compile_cache as cc
    cache = cc.CompileCache({cachedir!r})
    jitted = jax.jit(lambda x: x * 977.0 + 1.0)
    x = jnp.ones((8, 8), jnp.float32)
    lowered = jitted.lower(x)
    key = cache.key_for(lowered)
    ok = cache.store(key, lowered.compile(), surface="t")
    fn = cache.load(key)
    assert fn is not None, "entry unreadable after concurrent store"
    print(json.dumps({{"ok": bool(ok), "key": key}}))
""")


@pytest.mark.host_mesh
def test_two_process_write_dedupe(tmp_path):
    """Two processes compile + store the SAME program concurrently:
    both succeed, both can read the entry back, exactly one complete
    entry exists, and no staging debris is left behind."""
    cachedir = str(tmp_path / "cache")
    script = _WRITER.format(
        repo=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        cachedir=cachedir)
    procs = [subprocess.Popen([sys.executable, "-c", script],
                              stdout=subprocess.PIPE,
                              stderr=subprocess.PIPE, text=True)
             for _ in range(2)]
    outs = []
    for p in procs:
        out, err = p.communicate(timeout=180)
        assert p.returncode == 0, f"writer failed: {err}"
        outs.append(json.loads(out.strip().splitlines()[-1]))
    assert all(o["ok"] for o in outs)
    assert outs[0]["key"] == outs[1]["key"]     # deterministic key
    assert len(glob.glob(os.path.join(cachedir, "cc-*.json"))) == 1
    assert len(glob.glob(os.path.join(cachedir, "cc-*.exe"))) == 1
    assert not glob.glob(os.path.join(cachedir, "cc-staging-*"))


# ---------------------------------------------------------------------------
# kill-and-restart: 0 steady-state compiles, loss parity
# ---------------------------------------------------------------------------

_TRAINER = textwrap.dedent("""
    import os, sys, json
    os.environ["JAX_PLATFORMS"] = "cpu"
    sys.path.insert(0, {repo!r})
    import jax
    import jax.numpy as jnp
    import numpy as onp
    import mxnet_tpu as mx
    from mxnet_tpu import metrics as _m
    from mxnet_tpu.parallel import SPMDTrainer, make_mesh
    mx.random.seed(0)
    net = mx.gluon.nn.Dense(4)
    net.initialize()
    net(mx.np.zeros((2, 8)))
    trainer = SPMDTrainer(net, mx.gluon.loss.L2Loss(), "sgd",
                          {{"learning_rate": 0.05}},
                          mesh=make_mesh({{"dp": 1}},
                                         devices=jax.devices()[:1]))
    from mxnet_tpu.ndarray import random as _random
    _random.split_key()
    c0 = _m.COMPILE_MISSES.value
    losses = []
    for s in range(4):
        rng = onp.random.RandomState(100 + s)
        x = mx.np.array(rng.uniform(-1, 1, (8, 8)).astype("f4"))
        y = mx.np.array(rng.uniform(-1, 1, (8, 4)).astype("f4"))
        losses.append(float(trainer.step(x, y).asnumpy()))
        if {kill_after} >= 0 and s == {kill_after}:
            os.kill(os.getpid(), 9)        # SIGKILL mid-run, no cleanup
    print(json.dumps({{"losses": losses,
                       "compiles": _m.COMPILE_MISSES.value - c0}}))
""")


def _run_trainer(repo, cachedir, kill_after=-1):
    script = _TRAINER.format(repo=repo, kill_after=kill_after)
    env = dict(os.environ, MXNET_COMPILE_CACHE_DIR=cachedir)
    proc = subprocess.run([sys.executable, "-c", script], env=env,
                          capture_output=True, text=True, timeout=300)
    if kill_after >= 0:
        assert proc.returncode == -9
        return None
    assert proc.returncode == 0, f"trainer failed: {proc.stderr}"
    return json.loads(proc.stdout.strip().splitlines()[-1])


@pytest.mark.slow       # ci/run.sh cache-smoke gates this same path in
#                         tier1; the SIGKILL leg here additionally
#                         proves crash-consistency of the entry files
@pytest.mark.host_mesh
def test_kill_and_restart_zero_steady_state_compiles(tmp_path):
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    cachedir = str(tmp_path / "cache")
    refdir = str(tmp_path / "ref")
    # a job SIGKILLed mid-run leaves a usable (crash-consistent) cache
    _run_trainer(repo, cachedir, kill_after=1)
    assert glob.glob(os.path.join(cachedir, "cc-*.json"))
    # the restarted job: NO steady-state compiles, and losses
    # bit-identical to a never-killed cold reference run
    warm = _run_trainer(repo, cachedir)
    ref = _run_trainer(repo, refdir)
    assert warm["compiles"] == 0
    assert ref["compiles"] > 0
    assert warm["losses"] == ref["losses"]


# ---------------------------------------------------------------------------
# export artifact digest verification (serving load path)
# ---------------------------------------------------------------------------

def test_export_digest_verified_on_load(tmp_path):
    import mxnet_tpu as mx
    from mxnet_tpu import serving
    from mxnet_tpu.base import MXNetError

    mx.random.seed(0)
    net = mx.gluon.nn.Dense(3)
    net.initialize()
    net.hybridize()
    net(mx.np.zeros((1, 6), dtype="float32"))
    sym, params = net.export(str(tmp_path / "m"))
    with open(sym) as f:
        meta = json.load(f)
    assert "stablehlo_sha256" in meta and "params_sha256" in meta
    serving.load_served(str(tmp_path / "m"))        # intact: loads

    # garbled program: structured error naming the artifact, BEFORE
    # any deserializer runs
    bad = json.loads(json.dumps(meta))
    bad["stablehlo"] = bad["stablehlo"][:-8] + "AAAAAAA="
    with open(sym, "w") as f:
        json.dump(bad, f)
    with pytest.raises(MXNetError, match="program checksum"):
        serving.load_served(str(tmp_path / "m"))

    # garbled weights: named too
    with open(sym, "w") as f:
        json.dump(meta, f)
    with open(params, "r+b") as f:
        f.truncate(max(0, os.path.getsize(params) - 7))
    with pytest.raises(MXNetError, match="params_sha256|checksum"):
        serving.load_served(str(tmp_path / "m"))
