"""Share of SpMV device time spent gathering x: the ops under the
program's ``packsell.x_gather`` scope. The scope nests inside the decode
scopes, so ``decode_pct.spmv`` counts the same ops with the word
decode."""


def read(ctx):
    t = ctx["trace"]
    if ctx["kind"] != "spmv_synced":
        return None
    total = t.op_time_s()
    part = t.op_time_s(("packsell.x_gather",))
    if total <= 0 or part <= 0:
        return None
    return 100.0 * part / total
