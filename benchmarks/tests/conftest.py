"""The benchmark's own tests: run by hand and in the CPU rehearsal
(``JAX_PLATFORMS=cpu python -m pytest benchmarks/tests -q``), not in tier 1.
Four virtual CPU devices, so the harness can be walked with a sharded toy."""

import os
import sys

os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=4").strip()

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)
