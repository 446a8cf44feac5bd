"""One call of C = A^T B with A (K, M), B (K, N): the work any
implementation must do, whatever it tiles or re-reads."""


def flops(K: int, M: int, N: int) -> float:
    return 2.0 * M * N * K


def bytes_moved(K: int, M: int, N: int, itemsize: int = 4) -> float:
    """A and B read once, C written once."""
    return float(itemsize) * (K * M + K * N + M * N)


def least_s(K: int, M: int, N: int, peaks: dict, itemsize: int = 4) -> tuple:
    """(least seconds on the chip, which bound sets it): the larger of
    operations over the bf16 peak and bytes over HBM bandwidth."""
    t_ops = flops(K, M, N) / peaks["bf16_flops_per_s"]
    t_mem = bytes_moved(K, M, N, itemsize) / peaks["hbm_bytes_per_s"]
    return (t_ops, "compute") if t_ops >= t_mem else (t_mem, "memory")
