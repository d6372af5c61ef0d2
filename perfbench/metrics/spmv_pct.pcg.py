"""Share of a PCG set's device time spent under the program's SpMV
scopes (decode and epilogue); the rest is the solver's float64 dots,
axpys, the Jacobi step and the x unpermute, which no scope names yet."""

SPMV_SCOPES = ("packsell.fused_decode", "packsell.fused_kernel",
               "packsell.bucket_decode", "packsell.gather_epilogue")


def read(ctx):
    t = ctx["trace"]
    if ctx["kind"] != "pcg_sets":
        return None
    total = t.op_time_s()
    part = t.op_time_s(SPMV_SCOPES)
    if total <= 0 or part <= 0:
        return None
    return 100.0 * part / total
