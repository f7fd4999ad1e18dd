"""Test harness: simulate an 8-device TPU mesh on CPU (the reference's
DistributedTest multi-process harness, tests/unit/common.py:102, becomes a
virtual multi-device single process under XLA's host-platform device count)."""
import os

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
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.3)


@pytest.fixture(autouse=True)
def _reset_topology():
    from deepspeed_tpu.comm import reset_topology
    reset_topology()
    yield
    reset_topology()


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
