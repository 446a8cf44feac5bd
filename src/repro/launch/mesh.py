"""Production mesh definitions.

Single pod:  (data=16, model=16)            = 256 chips (TPU v5e pod)
Multi-pod:   (pod=2, data=16, model=16)     = 512 chips

Defined as functions so importing this module never touches jax device
state (the dry-run sets XLA_FLAGS before any jax initialization)."""
from __future__ import annotations

import jax


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes)


def make_host_mesh():
    """Degenerate 1-device mesh for CPU smoke tests."""
    return jax.make_mesh((1, 1), ("data", "model"))


# TPU v5e hardware constants (roofline denominators) for the pod dry-run
# only; the benchmark replaces them with a table keyed by device_kind.
PEAK_FLOPS_BF16 = 197e12          # per chip
HBM_BW = 819e9                    # bytes/s per chip
ICI_BW = 50e9                     # bytes/s per link
