"""jit'd wrapper: the compiled Pallas kernel; `interpret=True` runs it in
the Pallas interpreter (CPU tests only)."""
from __future__ import annotations

from functools import partial

import jax

from repro.kernels.tiled_matmul.kernel import tiled_matmul_pallas
from repro.kernels.tiled_matmul.ref import tiled_matmul_ref


@partial(jax.jit, static_argnames=("bm", "bn", "bk", "interpret"))
def tiled_matmul(a, b, *, bm: int = 256, bn: int = 256, bk: int = 256,
                 interpret: bool = False):
    """C = A^T B via the Pallas kernel."""
    return tiled_matmul_pallas(a, b, bm=bm, bn=bn, bk=bk, interpret=interpret)


__all__ = ["tiled_matmul", "tiled_matmul_ref"]
