"""SpMV's share of its HBM roofline: the format-minimum bytes of one call
(``work.spmv_min_bytes``) over the chip's peak bandwidth (``peaks.json``),
divided by the device time per call (busy time of the traced window over
the calls in it)."""
from perfbench import work


def read(ctx):
    t = ctx["trace"]
    if ctx["kind"] != "spmv_synced" or t.busy_s <= 0 or not ctx["peak"]:
        return None
    n_rows, n_cols = ctx["shape"]
    least_s = (work.spmv_min_bytes(n_rows, n_cols, ctx["nnz"])
               / ctx["peak"]["hbm_bytes_per_s"])
    return 100.0 * least_s / (t.busy_s / ctx["window"]["calls"])
