"""Oracle: each row's gated MLP through its group's expert, every expert
over every row and masked, in float32."""
from __future__ import annotations

import jax
import jax.numpy as jnp

ACTS = {"silu": jax.nn.silu,
        "gelu": lambda x: jax.nn.gelu(x, approximate=True)}


def moe_gmm_ref(x, w1, w3, w2, group_sizes, layer=None, *,
                act: str = "silu"):
    """x (N, D) sorted by expert; w1, w3 (E, D, F); w2 (E, F, D), or
    layer `layer` of stacks of them; group_sizes (E,).  Returns (N, D) in
    x.dtype, zeros past the total."""
    if layer is not None:
        w1, w3, w2 = w1[layer], w3[layer], w2[layer]
    hi = jax.lax.Precision.HIGHEST
    xf = x.astype(jnp.float32)
    ends = jnp.cumsum(group_sizes)
    expert = jnp.searchsorted(ends, jnp.arange(x.shape[0]), side="right")
    out = jnp.zeros(xf.shape, jnp.float32)
    for e in range(w1.shape[0]):
        h = ACTS[act](jnp.dot(xf, w1[e].astype(jnp.float32), precision=hi)) \
            * jnp.dot(xf, w3[e].astype(jnp.float32), precision=hi)
        y = jnp.dot(h, w2[e].astype(jnp.float32), precision=hi)
        out = out + jnp.where((expert == e)[:, None], y, 0.0)
    return out.astype(x.dtype)
