"""The benchmark's own tests run on the CPU with four virtual devices
(cell 3's path); the environment is fixed before jax starts a backend."""

import os
import sys

os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = (
    os.environ.get("XLA_FLAGS", "")
    + " --xla_force_host_platform_device_count=4"
    + " --xla_cpu_enable_concurrency_optimized_scheduler=false")

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)
