"""The work an algorithm needs, as functions of shapes: operations and
bytes, whatever implements them.

The first layer counts each live client's slice only: client ``i``
multiplies its ``[B, F_i]`` columns by its ``[F_i, H]`` rows of the
layer's kernel.  The zero-padded ``F x H`` product of the paper's
formulation is never counted, so a masked, sliced or kernel first
layer reads the same work.
"""
from __future__ import annotations

F32 = 4


def first_layer(rows: int, width: int, hidden: int, itemsize=F32):
    """(FLOPs, bytes) of one client's first-layer forward: x slice in,
    its kernel rows in, the [rows, hidden] product out."""
    flops = 2 * rows * width * hidden
    moved = itemsize * (rows * width + width * hidden + rows * hidden)
    return flops, moved


def least_seconds(flops, moved, peak: dict):
    """(seconds, bound): the least time the chip could take, and which
    of compute or memory bandwidth sets it."""
    t_c = flops / peak["bf16_flops_per_s"]
    t_m = moved / peak["hbm_bytes_per_s"]
    return (t_c, "compute") if t_c >= t_m else (t_m, "memory")


def step_flops_per_sample(widths, hidden, n_hidden, n_classes):
    """Matmul FLOPs of one De-VertiFL training step per sample, forward
    and backward, nothing recomputed.  ``widths`` are the live clients'
    slice widths.  Forward: each client's tower from its slice.
    Backward: both products of every layer (weights and inputs) except
    the first layer's input gradient, which no one needs."""
    first = sum(2 * w * hidden for w in widths)
    rest = len(widths) * (2 * hidden * hidden * (n_hidden - 1)
                          + 2 * hidden * n_classes)
    return first + rest + (first + 2 * rest)
