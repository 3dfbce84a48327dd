"""Test harness: simulate an 8-chip mesh on CPU.

The reference tests against local-mode Spark with real GPUs
(``/root/reference/python/tests/conftest.py:34-51``), emulating a
multi-node-multi-GPU cluster on one box. The TPU-native equivalent is
``--xla_force_host_platform_device_count``: 8 virtual CPU devices form a
mesh with the same SPMD program (and collectives) a v5e-8 slice would run.
"""

import os

# Must run before jax initializes its backends: the suite always runs on the
# CPU backend with 8 virtual devices, whatever the environment names.
os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import jax  # noqa: E402

from spark_rapids_ml_tpu.utils.platform import enable_compile_cache  # noqa: E402

# Persistent compilation cache (the one rule in utils/platform.py): the
# tree-builder programs dominate suite wall-clock; caching compiled
# executables on disk makes repeat runs on the same machine start warm.
_cache_dir = enable_compile_cache(min_compile_secs=0.5)
# One cache directory per xdist worker. JAX writes an entry in place
# (``Path.write_bytes``, no rename, and no lock unless eviction is on), so a
# worker that looks up a program while another worker is still writing the same
# program's entry reads half a file, and XLA:CPU's AOT loader aborts the
# process on it: ``tests/test_gbt.py::test_regressor_matches_sklearn_r2`` died
# so under six workers (``Fatal Python error: Aborted`` inside ``gbt_round``,
# right after the loader's lines) and passes alone. Workers run different
# files (``--dist loadfile``), so they share few programs anyway.
_worker = os.environ.get("PYTEST_XDIST_WORKER")
if _worker:
    jax.config.update(
        "jax_compilation_cache_dir", os.path.join(_cache_dir, _worker)
    )

import threading  # noqa: E402

import numpy as np  # noqa: E402
import pytest  # noqa: E402

assert jax.default_backend() == "cpu"
assert len(jax.devices()) == 8, f"expected 8 virtual devices, got {len(jax.devices())}"


@pytest.fixture(params=[1, 2, 4])
def n_workers(request):
    """Parametrized worker counts, like the reference's ``gpu_number``."""
    return request.param


@pytest.fixture
def rng():
    return np.random.default_rng(42)


def pytest_addoption(parser):
    parser.addoption(
        "--runslow", action="store_true", default=False, help="run slow tests"
    )


def pytest_configure(config):
    config.addinivalue_line("markers", "slow: mark test as slow")
    config.addinivalue_line("markers", "compat: CPU-oracle equivalence test")
    config.addinivalue_line(
        "markers",
        "allow_threads: test intentionally leaves named threads running",
    )


@pytest.fixture(autouse=True)
def _thread_leak_sanitizer(request):
    """Fail any test that leaks a live non-daemon thread.

    Snapshot-diff by thread name around each test: a non-daemon thread
    still alive afterwards means a missed ``close()``/``drain()`` —
    exactly the leak that hangs interpreter exit in production and
    bleeds scheduler/serving state into the next test. Daemon threads
    get a short grace join (dispatcher loops observe their shutdown
    flag within a tick) and are tolerated if still winding down —
    TPU012 already guarantees they cannot block exit. Opt out with
    ``@pytest.mark.allow_threads`` and a reason in the test body.
    """
    before = {t.name for t in threading.enumerate()}
    yield
    if request.node.get_closest_marker("allow_threads"):
        return
    leaked = [
        t
        for t in threading.enumerate()
        if t.is_alive() and not t.daemon and t.name not in before
    ]
    for t in leaked:
        t.join(timeout=2.0)
    leaked = [t for t in leaked if t.is_alive()]
    assert not leaked, (
        "test leaked live non-daemon thread(s): "
        f"{sorted(t.name for t in leaked)} — close/drain the owner, or "
        "mark the test @pytest.mark.allow_threads with a reason"
    )


def pytest_collection_modifyitems(config, items):
    if config.getoption("--runslow"):
        return
    skip_slow = pytest.mark.skip(reason="need --runslow option to run")
    for item in items:
        if "slow" in item.keywords:
            item.add_marker(skip_slow)
