"""The distributed SpMV's share of one chip's HBM roofline, inside the PCG
sets. Each chip holds n / P rows; per matvec it must read its share of
the words (nnz / P), of its own x (n / P) and of the halo (the program's
gauge ``dist.halo_entries`` / P, the entries the exchange fills, summed
over shards), and write its y (n / P): ``work.spmv_min_bytes`` of that
share, with P the gauge ``dist.shards`` and n, nnz the CSR's. That least
time at the chip's peak bandwidth (``peaks.json``) is divided by the
device time under the SpMV scopes (``spmv_pct.pcg4``'s share of the ops'
time, averaged over the chips) per matvec: one per iteration and one per
set (its initial residual). A program without the gauges reads None."""
from perfbench import recorder, registry, work


def read(ctx):
    t = ctx["trace"]
    if ctx["kind"] != "pcg_sets_dist" or not ctx["peak"]:
        return None
    shards = recorder.gauge("dist.shards")
    halo = recorder.gauge("dist.halo_entries")
    spmv_pct = registry.load_metric("spmv_pct.pcg4")(ctx)
    if not shards or halo is None or spmv_pct is None:
        return None
    n_rows, n_cols = ctx["shape"]
    least_s = (work.spmv_min_bytes(n_rows / shards,
                                   (n_cols + halo) / shards,
                                   ctx["nnz"] / shards)
               / ctx["peak"]["hbm_bytes_per_s"])
    spmv_s = spmv_pct / 100.0 * t.op_time_s()
    w = ctx["window"]
    return 100.0 * least_s / (spmv_s / (w["iterations"] + w["calls"]))
