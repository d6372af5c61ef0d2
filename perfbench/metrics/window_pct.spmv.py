"""Share of the decoded words whose x value the windowed gather serves:
100 × (1 − gauge ``plan.residual_words`` ÷ gauge ``plan.decode_words``),
the words left to the scalar gather being the residual; 0 where the plan's
gauge ``plan.window_width`` reads 0 (the scalar gather serves every
word). A program without these gauges reads None."""
from perfbench import recorder


def read(ctx):
    if ctx["kind"] != "spmv_synced":
        return None
    width = recorder.gauge("plan.window_width")
    if width is None:
        return None
    if width == 0:
        return 0.0
    words = recorder.gauge("plan.decode_words")
    resid = recorder.gauge("plan.residual_words")
    if not words or resid is None:
        return None
    return 100.0 * (1.0 - resid / words)
