"""benchmarks/tests run by hand (``python -m pytest benchmarks/tests -q``),
not in tier-1.  They rehearse on the CPU: four virtual devices, set before
jax starts, and the benchmark's directory on the path as run.py puts it."""
import os
import sys

os.environ["JAX_PLATFORMS"] = "cpu"
os.environ.setdefault("XLA_FLAGS",
                      "--xla_force_host_platform_device_count=4")
BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [BENCH_DIR, os.path.dirname(BENCH_DIR)]
