"""Share of a distributed PCG set's device time spent under the program's
SpMV scopes, averaged over the chips: each shard's member decodes and
their tails, and the composite's per-term inverse-permutation gathers
(``packsell.gather_epilogue``). The halo exchange (``halo_pct.pcg4``) and
the solver's vector work with its all-reduces are outside them."""

SPMV_SCOPES = ("packsell.fused_decode", "packsell.fused_kernel",
               "packsell.bucket_decode", "packsell.gather_epilogue")


def read(ctx):
    t = ctx["trace"]
    if ctx["kind"] != "pcg_sets_dist":
        return None
    total = t.op_time_s()
    part = t.op_time_s(SPMV_SCOPES)
    if total <= 0 or part <= 0:
        return None
    return 100.0 * part / total
