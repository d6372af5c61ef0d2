"""Read what the program's own recorder (``repro.observe.metrics``) holds
in this process: the gauges a plan sets when it is built, and the
``span_s`` histograms of the program's host spans.

``run.py`` turns the recorder on before set-up and never resets it, and a
run builds one plan, so each series describes this run. A program that
records no such series (one older than its spans and gauges) reads None.
"""
from __future__ import annotations


def _raw() -> dict:
    from repro.observe import metrics

    return metrics.raw_snapshot()


def gauge(name: str):
    """The value of gauge ``name``, whatever its labels; None where no
    plan set it, or where two plans set different values."""
    values = {v for (n, _), v in _raw()["gauges"].items() if n == name}
    return values.pop() if len(values) == 1 else None


def span(name: str, **labels):
    """The ``span_s`` histogram (count, sum, p50, ...) of host span
    ``name`` with exactly ``labels``; None where the program has none."""
    key = ("span_s", tuple(sorted(
        (k, str(v)) for k, v in dict(labels, span=name).items())))
    return _raw()["histograms"].get(key)
