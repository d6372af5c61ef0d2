"""Device idle share of a window of PCG sets (``trace.idle_pct``)."""
from perfbench import trace


def read(ctx):
    return trace.idle_pct(ctx, "pcg_sets")
