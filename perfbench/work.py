"""The work a kernel must do, computed from the CSR's shape alone.

A roofline numerator must not move when a change alters how the kernel is
implemented, so nothing here reads the plan, its layout, padding, dummies
or codec.
"""
from __future__ import annotations


def spmv_min_bytes(n_rows: int, n_cols: int, nnz: int) -> int:
    """The format-minimum bytes of one SpMV: one 32-bit PackSELL word per
    nonzero (the format's own definition), one float32 read of each x
    entry and one float32 write of each y entry."""
    return 4 * int(nnz) + 4 * int(n_cols) + 4 * int(n_rows)
