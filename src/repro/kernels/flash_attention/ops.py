"""jit'd wrapper: the compiled Pallas kernel; `interpret=True` runs it in
the Pallas interpreter (CPU tests only)."""
from __future__ import annotations

from functools import partial

import jax

from repro.kernels.flash_attention.kernel import flash_attention_pallas
from repro.kernels.flash_attention.ref import flash_attention_ref


@partial(jax.jit, static_argnames=("causal", "window", "logit_softcap",
                                   "scale", "bq", "bk", "interpret"))
def flash_attention(q, k, v, *, causal: bool = True, window: int = 0,
                    logit_softcap: float = 0.0, scale: float = None,
                    bq: int = 512, bk: int = 512, interpret: bool = False):
    return flash_attention_pallas(q, k, v, causal=causal, window=window,
                                  logit_softcap=logit_softcap, scale=scale,
                                  bq=bq, bk=bk, interpret=interpret)


__all__ = ["flash_attention", "flash_attention_ref"]
