"""The benchmark's own tests run on the CPU, on four virtual devices
(the dp=4 cell), with JAX's compile cache outside the checkout.  They
are not part of tier-1:  python -m pytest benchmark/tests -q"""
import os
import sys
import tempfile

os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ.setdefault(
    "XLA_FLAGS", "--xla_force_host_platform_device_count=4")
os.environ.setdefault(
    "JAX_COMPILATION_CACHE_DIR",
    os.path.join(tempfile.gettempdir(), "benchmark_tests_jax_cache"))
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)
