"""Test configuration.

IMPORTANT: no XLA device-count overrides here — smoke tests and benches must
see 1 CPU device (the dry-run sets its own override as its first import, and
tests/test_distribution.py re-execs itself in a subprocess with 8 devices).
"""
import os

# keep kernel dispatch on the ref path for model-level tests (the Pallas
# kernels are validated explicitly in tests/test_kernels.py via interpret)
os.environ.setdefault("REPRO_KERNEL_MODE", "ref")

# tests compile in-process through entry points that turn on JAX's
# persistent compilation cache; keep the suite hermetic and off the disk
os.environ.setdefault("JAX_ENABLE_COMPILATION_CACHE", "false")
