"""Device idle share of a window of distributed PCG sets, over the chips
of the mesh (``trace.idle_pct``; busy time is averaged over the chips)."""
from perfbench import trace


def read(ctx):
    return trace.idle_pct(ctx, "pcg_sets_dist")
