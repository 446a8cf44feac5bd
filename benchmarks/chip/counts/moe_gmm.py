"""The expert products of MoE layers (`moe_gmm`): for `rows` routed rows
over `experts_hit` experts with at least one row, each a gated MLP of
width F on model width D, the work any implementation must do: the three
products of every row, each hit expert's three weight matrices read
once, and each row read, its hidden product kept and its output written
(3 D values a row), all in bfloat16."""

BYTES = 2                      # bfloat16


def flops(rows: int, D: int, F: int) -> float:
    return 2.0 * 3 * rows * D * F


def bytes_moved(rows: int, experts_hit: int, D: int, F: int) -> float:
    return float(BYTES) * (experts_hit * 3 * D * F + rows * 3 * D)


def least_s(rows: int, experts_hit: int, D: int, F: int,
            peaks: dict) -> tuple:
    """(least seconds on the chip, which bound sets it): the larger of
    operations over the bf16 peak and bytes over HBM bandwidth."""
    t_ops = flops(rows, D, F) / peaks["bf16_flops_per_s"]
    t_mem = bytes_moved(rows, experts_hit, D, F) / peaks["hbm_bytes_per_s"]
    return (t_ops, "compute") if t_ops >= t_mem else (t_mem, "memory")
