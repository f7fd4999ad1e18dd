"""Accelerator abstraction (reference: accelerator/abstract_accelerator.py:10
``DeepSpeedAccelerator`` ABC + accelerator/real_accelerator.py:45 ``get_accelerator``).

JAX already abstracts the backend, so this layer is thin: device enumeration,
memory stats, dtype support, RNG, and the communication backend name.  The
``DS_ACCELERATOR`` env override is honoured like the reference's.
"""
import os
from typing import Optional

import jax
import jax.numpy as jnp


class Accelerator:
    """Base accelerator over a JAX backend."""

    def __init__(self, platform: str):
        self._platform = platform
        self._name = platform

    # ----- identity ---------------------------------------------------------
    def device_name(self, device_index: Optional[int] = None) -> str:
        if device_index is None:
            return self._name
        return f"{self._name}:{device_index}"

    def device(self, device_index: int = 0):
        return self.devices()[device_index]

    def devices(self):
        return [d for d in jax.devices() if d.platform == self._platform] or jax.devices()

    def device_count(self) -> int:
        return len(self.devices())

    def local_devices(self):
        return [d for d in jax.local_devices()
                if d.platform == self._platform] or jax.local_devices()

    def local_device_count(self) -> int:
        return len(self.local_devices())

    def current_device(self):
        return self.devices()[0]

    def is_available(self) -> bool:
        try:
            return self.device_count() > 0
        except RuntimeError:
            return False

    def communication_backend_name(self) -> str:
        """XLA collectives over ICI/DCN — the NCCL-equivalent (reference
        cuda_accelerator.py:23 returns 'nccl')."""
        return "xla"

    # ----- dtype support ----------------------------------------------------
    def is_bf16_supported(self) -> bool:
        return True

    def is_fp16_supported(self) -> bool:
        return True

    def preferred_dtype(self):
        return jnp.bfloat16

    # ----- memory -----------------------------------------------------------
    def memory_stats(self, device_index: int = 0) -> dict:
        """Of the ``device_index``-th LOCAL device: a process reads the
        allocator of its own devices only."""
        dev = self.local_devices()[device_index]
        stats = getattr(dev, "memory_stats", lambda: None)()
        return stats or {}

    def memory_allocated(self, device_index: int = 0) -> int:
        return self.memory_stats(device_index).get("bytes_in_use", 0)

    def total_memory(self, device_index: int = 0) -> int:
        return self.memory_stats(device_index).get("bytes_limit", 0)

    def available_memory(self, device_index: int = 0) -> int:
        s = self.memory_stats(device_index)
        return s.get("bytes_limit", 0) - s.get("bytes_in_use", 0)

    def empty_cache(self):
        pass

    # ----- RNG ---------------------------------------------------------------
    def default_rng(self, seed: int = 0):
        return jax.random.PRNGKey(seed)

    # ----- synchronisation ---------------------------------------------------
    def synchronize(self, obj=None):
        if obj is not None:
            jax.block_until_ready(obj)

    # ----- profiler ranges (reference: nvtx range_push/pop) ------------------
    def range_push(self, msg: str):
        self._trace_ctx = jax.profiler.TraceAnnotation(msg)
        self._trace_ctx.__enter__()

    def range_pop(self):
        ctx = getattr(self, "_trace_ctx", None)
        if ctx is not None:
            ctx.__exit__(None, None, None)
            self._trace_ctx = None

    def on_accelerator(self, tensor) -> bool:
        try:
            return any(d.platform == self._platform for d in tensor.devices())
        except Exception:
            return False


class TPU_Accelerator(Accelerator):
    def __init__(self):
        super().__init__("tpu")


class CPU_Accelerator(Accelerator):
    def __init__(self):
        super().__init__("cpu")

    def preferred_dtype(self):
        return jnp.float32


_ACCELERATOR: Optional[Accelerator] = None


def _detect() -> Accelerator:
    override = os.environ.get("DS_ACCELERATOR")
    if override == "cpu":
        return CPU_Accelerator()
    if override == "tpu":
        return TPU_Accelerator()
    platforms = {d.platform for d in jax.devices()}
    if "tpu" in platforms:
        return TPU_Accelerator()
    if "cpu" in platforms:
        return CPU_Accelerator()
    raise RuntimeError(
        f"deepspeed_tpu runs on TPU or CPU devices; jax reports {platforms}")


def get_accelerator() -> Accelerator:
    global _ACCELERATOR
    if _ACCELERATOR is None:
        _ACCELERATOR = _detect()
    return _ACCELERATOR


def set_accelerator(acc: Accelerator):
    global _ACCELERATOR
    _ACCELERATOR = acc
