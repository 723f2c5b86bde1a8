"""Operations, bytes and least time of the work the device is given,
computed from shapes alone: the yardstick of the roofline and MFU
metrics."""
from __future__ import annotations

from typing import Sequence

NODE_BYTES = 20          # feat, thr, left, right, value: 4 B each


def forest_launch_bytes(group_nodes: Sequence[int], rows: int,
                        n_features: int, n_trees: int) -> int:
    """Bytes a grouped forest launch must move at the least: the unpadded
    node tables of the groups it touches, each row's float32 features and
    each row's float32 leaf value per tree."""
    return (NODE_BYTES * int(sum(group_nodes)) + 4 * rows * n_features
            + 4 * rows * n_trees)


def least_time(ops: float, nbytes: float, peak_ops: float,
               peak_bytes: float):
    """``(seconds, bound)``: the larger of ops over the peak rate and
    bytes over the peak bandwidth, and which of the two it is."""
    t_ops, t_bytes = ops / peak_ops, nbytes / peak_bytes
    return (t_bytes, "bytes") if t_bytes >= t_ops else (t_ops, "ops")


def dnn_flops(n_features: int, layers: Sequence[int]) -> int:
    widths = [n_features] + list(layers)
    return 2 * sum(a * b for a, b in zip(widths, widths[1:]))


def linear_flops(n_features: int) -> int:
    return 2 * (n_features + 1)
