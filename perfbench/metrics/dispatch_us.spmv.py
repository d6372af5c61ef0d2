"""Host dispatch: the median host time of one ``SpMVPlan.spmv`` call, in
microseconds, from the program's host span ``packsell.dispatch`` (the
counter bump, the operand lookup and the jitted call's dispatch; the
device work is not waited on)."""
from perfbench import recorder


def read(ctx):
    if ctx["kind"] != "spmv_synced":
        return None
    h = recorder.span("packsell.dispatch", kind="spmv")
    return None if h is None else h["p50"] * 1e6
