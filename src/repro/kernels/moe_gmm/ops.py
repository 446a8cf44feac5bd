"""jit'd wrapper: the compiled Pallas kernel on a TPU; `interpret=True`
runs it in the Pallas interpreter (the CPU tests, and the model on a
host without a TPU)."""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.kernels.moe_gmm.kernel import moe_gmm_pallas
from repro.kernels.moe_gmm.ref import moe_gmm_ref


def row_tile(n: int) -> int:
    """128-row tiles, or the rows rounded up to bf16's 16-row tile where
    there are fewer (a decode step's few rows)."""
    return 128 if n >= 128 else max(16, -(-n // 16) * 16)


@partial(jax.jit, static_argnames=("act", "tm", "bf", "interpret"))
def moe_gmm(x, w1, w3, w2, group_sizes, layer=None, *, act: str = "silu",
            tm: int | None = None, bf: int | None = None,
            interpret: bool = False):
    """x (N, D) sorted by expert, `group_sizes[e]` rows for expert e of
    w1, w3 (E, D, F) and w2 (E, F, D), or of layer `layer` of stacks
    (L, E, D, F) and (L, E, F, D), read in place.  Returns (N, D): each
    grouped row's gated MLP through its expert, zeros past the groups'
    total.  No expert's weights are read where no row is routed to it."""
    N, D = x.shape
    tm = tm or row_tile(N)
    pad = -N % tm
    xp = jnp.pad(x, ((0, pad), (0, 0))) if pad else x
    total = jnp.sum(group_sizes)

    def run(xp):
        return moe_gmm_pallas(xp, w1, w3, w2, group_sizes, layer, act=act,
                              tm=tm, bf=bf, interpret=interpret)

    out = jax.lax.cond(total > 0, run, jnp.zeros_like, xp)[:N]
    return jnp.where((jnp.arange(N) < total)[:, None], out, 0)


__all__ = ["moe_gmm", "moe_gmm_ref", "row_tile"]
