"""Test harness: simulate an 8-device TPU mesh on CPU (the reference's
DistributedTest multi-process harness, tests/unit/common.py:102, becomes a
virtual multi-device single process under XLA's host-platform device count)."""
import contextlib
import json
import os
import signal
import sys
import tempfile
import time

# must run before jax initialises its backends: tests always run on the
# virtual CPU mesh, whatever the machine holds
os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    _flags = (_flags + " --xla_force_host_platform_device_count=8").strip()
if "xla_backend_optimization_level" not in _flags:
    # tests measure correctness, not codegen quality: backend opt level 0
    # cuts CPU compile time ~33% on this suite (compile-bound on 1 core)
    _flags = (_flags + " --xla_backend_optimization_level=0").strip()
if "xla_cpu_use_thunk_runtime" not in _flags:
    # this jaxlib's new CPU thunk runtime corrupts the glibc heap under
    # the engine's donated train steps with torch loaded in-process
    # ("corrupted size vs. prev_size" → SIGSEGV kills the whole pytest
    # run at a random later test); the legacy runtime is stable
    _flags = (_flags + " --xla_cpu_use_thunk_runtime=false").strip()
os.environ["XLA_FLAGS"] = _flags

import jax  # noqa: E402
import pytest  # noqa: E402

from deepspeed_tpu.utils.compile_cache import enable_compile_cache  # noqa: E402

# persistent compilation cache: the suite compiles many near-identical
# engine steps on the virtual CPU mesh; caching keeps the full-suite wall
# time inside the driver's budget (and repeat runs mostly free)
enable_compile_cache()
jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)


#: no test may wait longer: four times the longest measured under six
#: workers' load; every subprocess / thread / HTTP wait in tests/ trips
#: first (tests/test_wait_budget.py holds them to 240 s)
TEST_LIMIT_S = 300
_INFLIGHT_DIR = os.path.join(tempfile.gettempdir(), "ds_tier1_inflight")
os.makedirs(_INFLIGHT_DIR, exist_ok=True)


def _note_inflight(text):
    worker = os.environ.get("PYTEST_XDIST_WORKER", "main")
    with open(os.path.join(_INFLIGHT_DIR, worker), "w") as f:
        f.write(f"{time.strftime('%H:%M:%S')} {text}\n")


def pytest_configure(config):
    # ``--dist loadfile`` hands a worker one file at a time, by default
    # the files with the most tests first: a file of three engine tests
    # then starts last and ends alone while five workers idle.  Hand them
    # out in the order of the collection, which the hook below makes the
    # order of their measured seconds.
    if hasattr(config.option, "loadscopereorder"):
        config.option.loadscopereorder = False


def pytest_collection_modifyitems(items):
    """The heaviest files first (longest processing time first), a file's
    tests together and in their own order; a file the table does not have
    goes before them all.  ``scripts/tier1_seconds.py`` writes the table."""
    table = os.path.join(os.path.dirname(__file__), "data",
                         "tier1_file_seconds.json")
    with open(table) as f:
        seconds = json.load(f)
    items.sort(key=lambda item: -seconds.get(
        item.nodeid.split("::")[0], float("inf")))


@pytest.hookimpl(hookwrapper=True)
def pytest_runtest_protocol(item, nextitem):
    """One limit for every test, its module's fixtures included: a test
    that waits fails by its name with its stack instead of eating the
    run's clock (xdist runs tests on the worker's main thread, where the
    signal lands).  The worker's note says which test a cut run was in."""
    def over(signum, frame):
        raise TimeoutError(f"{item.nodeid} ran over {TEST_LIMIT_S} s")

    _note_inflight(item.nodeid)
    was = signal.signal(signal.SIGALRM, over)
    signal.setitimer(signal.ITIMER_REAL, TEST_LIMIT_S)
    try:
        yield
    finally:
        signal.setitimer(signal.ITIMER_REAL, 0)
        signal.signal(signal.SIGALRM, was)
        _note_inflight(f"idle after {item.nodeid}")


def pytest_sessionstart(session):
    if "PYTEST_XDIST_WORKER" in os.environ:      # the controller only
        return
    for stale in os.listdir(_INFLIGHT_DIR):
        with contextlib.suppress(FileNotFoundError):    # another session's
            os.remove(os.path.join(_INFLIGHT_DIR, stale))

    def say_inflight_and_die(signum, frame):
        # the command's `timeout` sends SIGTERM: the log then names the
        # tests the clock caught, one line a worker
        capture = session.config.pluginmanager.getplugin("capturemanager")
        if capture is not None:
            capture.suspend_global_capture(in_=True)
        for worker in sorted(os.listdir(_INFLIGHT_DIR)):
            with open(os.path.join(_INFLIGHT_DIR, worker)) as f:
                sys.stderr.write(f"\nin flight [{worker}] {f.read()}")
        sys.stderr.flush()
        signal.signal(signal.SIGTERM, signal.SIG_DFL)
        os.kill(os.getpid(), signal.SIGTERM)

    signal.signal(signal.SIGTERM, say_inflight_and_die)


@pytest.fixture(autouse=True)
def _reset_topology():
    from deepspeed_tpu.comm import reset_topology
    reset_topology()
    yield
    reset_topology()


@pytest.fixture(autouse=True)
def _no_leaked_moe_tap():
    """A serving scheduler installs its registry as the expert layers'
    routing tap when it is built — a global of moe/layer.py that nobody
    takes out (ROADMAP D28) — and a train step traced while one is
    installed holds host callbacks and is traced again at every call.  No
    test starts with, or leaves behind, another test's tap."""
    from deepspeed_tpu.moe.layer import set_moe_metrics_registry
    set_moe_metrics_registry(None)
    yield
    set_moe_metrics_registry(None)


@pytest.fixture
def interpret_pallas(monkeypatch):
    """Run every Pallas kernel in interpret mode (the CPU has no Mosaic)."""
    import functools
    from jax.experimental import pallas as pl
    monkeypatch.setattr(
        pl, "pallas_call", functools.partial(pl.pallas_call, interpret=True))


@pytest.fixture
def devices8():
    devs = jax.devices()
    assert len(devs) == 8, f"expected 8 simulated devices, got {len(devs)}"
    return devs
