"""Host build of the distributed plan: seconds the program spends in
``build_dist_plan`` (partition, per-shard members, stacking, placement on
the mesh), from its own host span ``packsell.dist_build``. A program
without the span reads None."""
from perfbench import recorder


def read(ctx):
    h = recorder.span("packsell.dist_build")
    return None if h is None else h["sum"]
