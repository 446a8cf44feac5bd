"""jit'd wrapper: the compiled Pallas kernel; `interpret=True` runs it in
the Pallas interpreter (CPU tests only)."""
from __future__ import annotations

from functools import partial

import jax

from repro.kernels.rwkv6_scan.kernel import wkv6_pallas
from repro.kernels.rwkv6_scan.ref import wkv6_ref, wkv6_sequential_ref


@partial(jax.jit, static_argnames=("chunk", "interpret"))
def wkv6(r, k, v, logw, u, *, chunk: int = 32, interpret: bool = False):
    return wkv6_pallas(r, k, v, logw, u, chunk=chunk, interpret=interpret)


__all__ = ["wkv6", "wkv6_ref", "wkv6_sequential_ref"]
