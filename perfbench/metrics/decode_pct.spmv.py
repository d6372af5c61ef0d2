"""Share of SpMV device time spent under the program's decode scopes:
the fused decode (XLA body), the fused Pallas kernel and the bucketed or
cursor-cache decode. The rest is the inverse-permutation epilogue and
whatever no scope names."""

DECODE_SCOPES = ("packsell.fused_decode", "packsell.fused_kernel",
                 "packsell.bucket_decode")


def read(ctx):
    t = ctx["trace"]
    if ctx["kind"] != "spmv_synced":
        return None
    total = t.op_time_s()
    part = t.op_time_s(DECODE_SCOPES)
    if total <= 0 or part <= 0:
        return None
    return 100.0 * part / total
