"""The benchmark's own tests, on the CPU: four virtual devices for the
mesh cell, and the harness and the program on the path.

    python -m pytest -q benchmarks/chip/tests
"""
import os
import sys
from pathlib import Path

os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=4")

HERE = Path(__file__).resolve().parent
for p in (str(HERE.parents[2] / "src"), str(HERE.parent)):
    if p not in sys.path:
        sys.path.insert(0, p)
