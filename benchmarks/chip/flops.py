"""Operations and bytes one step *needs*, from shapes alone.

These count the rows a batch touches — gather, scatter, the optimizer's
update of those rows, the dense layers — and never a pass over the whole
``[F, D]`` table: a program that makes such a pass (dense Adam does) spends
time these functions do not credit, which is what ``step_mfu`` is for.
Counts are per batch of ``rows`` rows holding ``nnz`` values; float32
everywhere (4 bytes).  Each function returns ``(flops, bytes)``.
"""

from __future__ import annotations

F32 = 4


def fm_forward(rows: int, nnz: int, dim: int):
    # gather v and w rows; vx, sum, vx*vx, sum per value and factor;
    # 0.5 * sum(s1*s1 - s2) per row
    flops = 4 * nnz * dim + 2 * nnz + 3 * rows * dim
    bytes_ = nnz * (dim + 1) * F32 + nnz * 2 * F32 + rows * F32
    return flops, bytes_


def fm_train_step(rows: int, nnz: int, dim: int):
    """Forward, backward into the touched rows, Adam on the touched rows."""
    f_fwd, b_fwd = fm_forward(rows, nnz, dim)
    # dL/dv[id] += x * (s1[row] - v*x) * g[row]: 4 flops per value and factor
    f_bwd = 4 * nnz * dim + 2 * nnz + 8 * rows
    b_bwd = 2 * nnz * (dim + 1) * F32          # read-modify-write the grads
    # Adam per touched element: m, v, p read and written, g read; ~12 flops
    f_opt = 12 * nnz * (dim + 1)
    b_opt = 7 * nnz * (dim + 1) * F32
    return f_fwd + f_bwd + f_opt, b_fwd + b_bwd + b_opt + 2 * rows * F32


def dcn_forward(rows: int, nnz: int, dim: int, layers: int):
    # embedding bag + linear term, then per layer x0 * (x @ W + b) + x
    flops = (2 * nnz * dim + 2 * nnz
             + layers * (2 * rows * dim * dim + 3 * rows * dim)
             + 2 * rows * dim)
    bytes_ = (nnz * (dim + 1) * F32 + nnz * 2 * F32
              + layers * (dim * dim + dim) * F32 + rows * F32)
    return flops, bytes_


def least_seconds(flops: float, bytes_: float, peaks: dict):
    """(seconds, which bound): the larger of operations over the chip's peak
    rate and bytes over its peak bandwidth."""
    tc = flops / peaks["bf16_flops_per_s"]
    tb = bytes_ / peaks["hbm_bytes_per_s"]
    return (tc, "compute") if tc >= tb else (tb, "bandwidth")
