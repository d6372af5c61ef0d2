"""Device idle share of an SpMV window (``trace.idle_pct``)."""
from perfbench import trace


def read(ctx):
    return trace.idle_pct(ctx, "spmv_synced")
