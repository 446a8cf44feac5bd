"""jit'd wrapper: the compiled Pallas kernel (n_groups == 1; grouped B/C
raise); `interpret=True` runs it in the Pallas interpreter (CPU tests
only)."""
from __future__ import annotations

from functools import partial

import jax

from repro.kernels.mamba2_ssd.kernel import ssd_pallas
from repro.kernels.mamba2_ssd.ref import ssd_ref, ssd_sequential_ref


@partial(jax.jit, static_argnames=("chunk", "interpret"))
def ssd(xdt, dA, B_, C_, *, chunk: int = 64, interpret: bool = False):
    return ssd_pallas(xdt, dA, B_, C_, chunk=chunk, interpret=interpret)


__all__ = ["ssd", "ssd_ref", "ssd_sequential_ref"]
