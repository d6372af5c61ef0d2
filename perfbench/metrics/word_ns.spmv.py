"""Device nanoseconds per decoded word: the device's busy time per SpMV
call over the plan's gauge ``plan.decode_words``, the 32-bit words the
executed decode streams per call (the fused stream on the checkpoint
path, the bucketed packs on the cursor path)."""
from perfbench import recorder


def read(ctx):
    t = ctx["trace"]
    if ctx["kind"] != "spmv_synced" or t.busy_s <= 0:
        return None
    words = recorder.gauge("plan.decode_words")
    if not words:
        return None
    return t.busy_s / ctx["window"]["calls"] / words * 1e9
