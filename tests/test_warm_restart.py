"""Warm restarts through jax's persistent compilation cache — the one
compile cache this repo has (``base.place_compile_cache`` gives it a
directory; ``metrics._install_jax_hooks`` counts what a boot compiled
and what it loaded).  Every chipbench cell's warm ``setup_s`` and
``compiled_in_window`` rest on three things held here:

* each program of the main path (trainer, export serving, both
  generation engines — the donated ones by name) compiled by one
  process is LOADED by the next, with byte-identical results;
* ``place_compile_cache`` puts the cache where the docs say;
* a load counts as ``mxnet_compile_persistent_hits_total`` and not as
  ``mxnet_compile_misses_total``, and ``/v1/model`` and
  ``tools/serve.py``'s banner show the directory and both counts.
"""
import json
import os
import subprocess
import sys

import jax
import pytest

import mxnet_tpu as mx
from mxnet_tpu import base, metrics, serving

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "tools"))

# ---------------------------------------------------------------------------
# warm restart, per program
# ---------------------------------------------------------------------------

PROGRAMS = {
    "trainer": ["spmd.step", "spmd.step_donated_inputs", "spmd.multi",
                "bulk.segment"],
    "served": ["served.batch1", "served.batch2", "served.batch4"],
    "gpt": ["gpt.prefill", "gpt.select", "gpt.prefill_suffix",
            "gpt.shrink_rows", "gpt.row_write", "gpt.step_donated",
            "gpt.self_draft", "gpt.verify_donated", "gpt.grow_rows",
            "gpt.engine"],
    "hybrid": ["hybrid.prefill",
               "hybrid.row_write_and_state_install_donated",
               "hybrid.step_donated", "hybrid.engine"],
}


@pytest.fixture(scope="module")
def boots(tmp_path_factory):
    """``{group: [cold report, warm report]}``: tests/warm_restart_child.py
    run twice for every group against the group's own cache directory —
    the four cold children side by side, then the four warm ones."""
    root = tmp_path_factory.mktemp("warm_restart")
    reports = {group: [] for group in PROGRAMS}
    for boot in ("cold", "warm"):
        procs = {}
        for group in PROGRAMS:
            work = root / group
            work.mkdir(exist_ok=True)
            env = dict(os.environ, JAX_PLATFORMS="cpu",
                       JAX_COMPILATION_CACHE_DIR=str(work / "cache"),
                       JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0",
                       JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES="-1")
            with open(work / f"{boot}.err", "w") as err:
                procs[group] = subprocess.Popen(
                    [sys.executable,
                     os.path.join(ROOT, "tests", "warm_restart_child.py"),
                     group, str(work)],
                    env=env, stdout=subprocess.PIPE, stderr=err, text=True)
        for group, proc in procs.items():
            out, _ = proc.communicate(timeout=600)
            assert proc.returncode == 0, (
                f"{boot} {group} child failed:\n"
                + (root / group / f"{boot}.err").read_text()[-2000:])
            report = json.loads(out.strip().splitlines()[-1])
            assert report["cache_dir"] == str(root / group / "cache")
            reports[group].append(report)
    return reports


@pytest.mark.host_mesh
@pytest.mark.parametrize(
    "group,program",
    [(g, p) for g, programs in PROGRAMS.items() for p in programs])
def test_program_is_loaded_by_the_next_process(boots, group, program):
    cold, warm = (boot["programs"][program] for boot in boots[group])
    # a second program of one process with the first's HLO is itself a
    # load (spmd.step's batch donation is pruned as unusable), so the
    # cold side is held by its sum
    assert cold["compiled"] + cold["loaded"] >= 1
    assert warm["compiled"] == 0
    assert warm["loaded"] == cold["compiled"] + cold["loaded"]
    assert warm["digest"] == cold["digest"]


@pytest.mark.host_mesh
@pytest.mark.parametrize("group", list(PROGRAMS))
def test_warm_boot_compiles_nothing(boots, group):
    """Eager helpers, initialisers and transfers included: the whole of
    a warm boot is loads."""
    cold, warm = (boot["total"] for boot in boots[group])
    assert cold["compiled"] > 0
    assert warm["compiled"] == 0 and warm["loaded"] > 0


# ---------------------------------------------------------------------------
# base.place_compile_cache
# ---------------------------------------------------------------------------

_CACHE_CONFIG = ("jax_platforms", "jax_compilation_cache_dir",
                 "jax_persistent_cache_min_compile_time_secs")


@pytest.fixture
def jax_config(monkeypatch):
    """jax.config as a process not pinned to the CPU has it at import,
    restored afterwards.  Nothing compiles in between."""
    saved = {name: getattr(jax.config, name) for name in _CACHE_CONFIG}
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    monkeypatch.delenv("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS",
                       raising=False)
    jax.config.update("jax_platforms", None)
    jax.config.update("jax_compilation_cache_dir", None)
    yield
    for name, value in saved.items():
        jax.config.update(name, value)


def test_place_cpu_pinned_process_gets_no_directory(jax_config):
    jax.config.update("jax_platforms", "cpu")
    before = jax.config.jax_persistent_cache_min_compile_time_secs
    base.place_compile_cache()
    assert jax.config.jax_compilation_cache_dir is None
    assert jax.config.jax_persistent_cache_min_compile_time_secs == before


def test_place_respects_jax_compilation_cache_dir(jax_config, monkeypatch,
                                                  tmp_path):
    # jax itself reads the variable into its config at import
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    jax.config.update("jax_compilation_cache_dir", str(tmp_path))
    base.place_compile_cache()
    assert jax.config.jax_compilation_cache_dir == str(tmp_path)


def test_place_defaults_to_the_checkout(jax_config):
    base.place_compile_cache()
    assert jax.config.jax_compilation_cache_dir == os.path.join(
        ROOT, ".jax_cache")


@pytest.mark.parametrize("env,want", [(None, 0.0), ("2.5", 2.5)])
def test_place_min_compile_time(jax_config, monkeypatch, env, want):
    """0 (sub-second programs are most of a warm start) unless the
    operator set jax's own variable."""
    if env is not None:
        monkeypatch.setenv("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", env)
    jax.config.update("jax_persistent_cache_min_compile_time_secs",
                      float(env or 1.0))
    base.place_compile_cache()
    assert jax.config.jax_persistent_cache_min_compile_time_secs == want


# ---------------------------------------------------------------------------
# what operators and chipbench read
# ---------------------------------------------------------------------------

def _counts():
    return (metrics.COMPILE_MISSES.value,
            metrics.COMPILE_PERSISTENT_HITS.value,
            metrics.hist_stats("mxnet_compile_seconds")[1])


@pytest.mark.parametrize("from_cache", [True, False])
def test_a_load_is_a_persistent_hit_and_not_a_miss(from_cache):
    """The rule ``compiled_in_window`` and "N compiled, M loaded" rest
    on, on jax's own events: a backend_compile duration that follows a
    cache hit on its thread is a load."""
    from jax import monitoring
    misses, hits, timed = _counts()
    if from_cache:
        monitoring.record_event("/jax/compilation_cache/cache_hits")
    monitoring.record_event_duration_secs(
        "/jax/core/compile/backend_compile_duration", 0.25)
    if from_cache:
        assert _counts() == (misses, hits + 1, timed)
    else:
        assert _counts() == (misses + 1, hits, timed + 1)


def _want_stats(directory):
    return {"dir": directory,
            "compiled": int(metrics.COMPILE_MISSES.value),
            "loaded": int(metrics.COMPILE_PERSISTENT_HITS.value)}


def test_v1_model_reports_the_cache(jax_config, tmp_path):
    mx.random.seed(0)
    net = mx.gluon.nn.Dense(3)
    net.initialize()
    net.hybridize()
    net(mx.np.zeros((1, 6), dtype="float32"))
    model = serving.load_served(net)
    server = serving.ModelServer(model, model.default_policy(max_batch=2),
                                 warmup=True)
    assert server.describe()["compile_cache"] == _want_stats(None)
    jax.config.update("jax_compilation_cache_dir", str(tmp_path))
    assert server.describe()["compile_cache"] == _want_stats(str(tmp_path))
    assert _want_stats(None)["compiled"] > 0


def test_serve_banner_reports_the_cache(jax_config, tmp_path):
    import serve
    stats = _want_stats(None)
    assert serve._cache_note() == (
        f"  [compile cache: off, {stats['compiled']} compiled / "
        f"{stats['loaded']} loaded this boot]")
    jax.config.update("jax_compilation_cache_dir", str(tmp_path))
    assert f"[compile cache: {tmp_path}, " in serve._cache_note()
