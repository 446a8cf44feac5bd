"""Plain reference of the atb-ensemble task: C = A^T B reduced by x, then
sums of those vectors, in float64 NumPy.  It imports nothing of the
program.

`control_task` is the reference put in the kernel's place at the next
precision below the configuration's (float32 at `highest`): three bf16
passes, what `Precision.HIGH` does on a TPU, written out so that it means
the same on any backend.  Its relative error is what a later change that
dropped to that precision would show, and the limits must fail it.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


def wave(A, B, X, tasks, *, group: int):
    """One wave's fan-out vectors, fan-in sums and root, in float64.

    tasks[t] = (i, j, r): task t computes (A_i^T B_j) X_r = A_i^T (B_j X_r).
    """
    X64 = np.asarray(X, np.float64)
    n = X64.shape[1]
    fan = np.zeros((len(tasks), n))
    by_pair: dict = {}
    for t, (i, j, r) in enumerate(tasks):
        by_pair.setdefault((i, j), []).append((t, r))
    for (i, j), trs in by_pair.items():
        ts = [t for t, _ in trs]
        xs = X64[[r for _, r in trs]].T                     # (n, k)
        a = np.asarray(A[i], np.float64)
        b = np.asarray(B[j], np.float64)
        fan[ts] = (a.T @ (b @ xs)).T
    fan_in = np.stack([fan[g:g + group].sum(0)
                       for g in range(0, len(tasks), group)])
    return fan, fan_in, fan_in.sum(0)


@jax.jit
def bf16x3(a, b):
    """A^T B from three bf16 products (hi*hi + hi*lo + lo*hi), summed in
    float32: the lo*lo term and the rounding of each part are lost.  The
    parts are rounded by `reduce_precision`, which XLA keeps: a round trip
    through bfloat16 inside a fusion may be left out on a TPU (excess
    precision is allowed there), which leaves lo = 0 and one pass."""
    def split(m):
        bf16 = lambda v: jax.lax.reduce_precision(      # noqa: E731
            v, exponent_bits=8, mantissa_bits=7)
        hi = bf16(m)
        return hi.astype(jnp.bfloat16), bf16(m - hi).astype(jnp.bfloat16)

    (ah, al), (bh, bl) = split(a), split(b)
    dot = lambda u, v: jax.lax.dot_general(          # noqa: E731
        u, v, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    return dot(ah, bh) + dot(ah, bl) + dot(al, bh)


def control_task(kernel, a, b, x):
    """The control, in place of the fan-out task: A^T B at three bf16
    passes, then reduced by x at full precision."""
    return jax.block_until_ready(
        jnp.dot(bf16x3(a, b), x, precision=jax.lax.Precision.HIGHEST))
