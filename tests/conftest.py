"""Test config: force an 8-device virtual CPU mesh before jax initializes.

Mirrors the reference's test strategy of running the op suite on a default
context switched by environment (SURVEY.md section 4): tests run on XLA:CPU
with 8 virtual devices so sharding/collective paths are exercised without
TPU hardware (the driver separately dry-runs multi-chip compilation).
"""
import os

# Must happen before jax backend initialization.
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8").strip()

import jax  # noqa: E402

_TEST_CTX = os.environ.get("MXNET_TEST_CTX", "cpu")

if _TEST_CTX != "tpu":
    # tests run on the virtual CPU mesh by default, whatever the
    # environment's JAX_PLATFORMS says (with it pinned to cpu,
    # base.place_compile_cache leaves jax's persistent cache off too)
    jax.config.update("jax_platforms", "cpu")
else:
    # TPU matmuls default to bf16 passes; the suite's tolerances assume
    # f32 math (the reference compared f32 CUDA kernels). 'highest' runs
    # f32-accurate matmuls — slower, but this is a correctness suite.
    jax.config.update("jax_default_matmul_precision", "highest")
# MXNET_TEST_CTX=tpu: the accelerator backend stays live and — because
# the implicit default context is the accelerator when one exists
# (context._implicit_default) — the WHOLE suite's default-ctx arrays and
# models run on the chip, the reference's test_operator_gpu.py ctx-flip
# ("the whole CPU suite reruns on GPU", SURVEY §4). `ci/run.sh tpu-unit`
# is the entry point.

import pytest  # noqa: E402


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "slow: excluded from the tier-1 time-budgeted selection "
        "(-m 'not slow'); run via ci/run.sh chaos / unit variants.")
    config.addinivalue_line(
        "markers",
        "host_mesh: needs the multi-device virtual CPU mesh or spawns "
        "multi-process CPU jobs; skipped under the MXNET_TEST_CTX=tpu "
        "ctx-flip (one real chip in the bench env). Mark any new "
        "multi-device test file with `pytestmark = pytest.mark."
        "host_mesh` — there is no central filename list to update.")


def pytest_collection_modifyitems(config, items):
    if _TEST_CTX != "tpu":
        return
    skip = pytest.mark.skip(
        reason="multi-device/multi-process test: needs the virtual CPU "
               "mesh (single chip in the bench env)")
    for item in items:
        if item.get_closest_marker("host_mesh") is not None:
            item.add_marker(skip)


@pytest.fixture(autouse=True)
def _fixed_seed():
    """Seed all RNGs per test (reference: tests/python/unittest/common.py
    with_seed); export MXNET_TEST_SEED to repro."""
    seed = int(os.environ.get("MXNET_TEST_SEED", "42"))
    import numpy as np
    import mxnet_tpu as mx
    np.random.seed(seed)
    mx.random.seed(seed)
    yield
