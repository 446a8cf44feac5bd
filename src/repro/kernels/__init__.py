"""Pallas TPU kernels for the framework's compute hot-spots.

  tiled_matmul    A^T B single-precision tiled matmul — the paper's METG
                  benchmark workload (§3), MXU-tiled for TPU
  flash_attention fused causal attention w/ online softmax (prefill path)
  rwkv6_scan      chunked WKV recurrence (RWKV6 time-mix inner loop)
  mamba2_ssd      chunked state-space-dual scan (Mamba2 inner loop)

Each kernel ships kernel.py (pl.pallas_call + BlockSpec VMEM tiling),
ops.py (jit'd wrapper; `interpret=True` only in the CPU tests), and ref.py
(pure-jnp oracle used by the models and the allclose test sweeps).
"""
