"""``window_pct.spmv``: the share of decoded words the program's windowed x
gather serves, read from the plan gauges ``plan.window_width``,
``plan.residual_words`` and ``plan.decode_words``. A program older than
those gauges reads None."""
from __future__ import annotations

import pytest

from perfbench import recorder, registry, trace


@pytest.fixture
def obs():
    """The program's recorder, on and empty; its state restored after."""
    from repro.observe import metrics

    prev = metrics.enable(True)
    metrics.reset()
    yield metrics
    metrics.reset()
    metrics.enable(prev)


def _ctx(kind):
    return {"kind": kind, "trace": trace.Summary((0, 10_000), [],
                                                 {0: [[0, 8_000]]}, []),
            "window": {"calls": 2}}


def _read(ctx):
    return registry.load_metric("window_pct.spmv")(ctx)


def test_reads_none_without_the_window_gauges(obs):
    # an older program sets the decode-word gauge but no window gauges
    obs.gauge("plan.decode_words", 1000, variant="jnp", codec="fp16",
              cache_mode="checkpoint")
    assert _read(_ctx("spmv_synced")) is None


def test_reads_the_recorded_gauges(obs):
    lab = dict(variant="jnp", codec="fp16", cache_mode="checkpoint")
    obs.gauge("plan.decode_words", 1000, **lab)
    obs.gauge("plan.window_width", 64, **lab)
    obs.gauge("plan.window_pairs", 1000 // 32, **lab)
    obs.gauge("plan.residual_words", 80, **lab)
    assert _read(_ctx("spmv_synced")) == pytest.approx(92.0)
    # an SpMV metric: a PCG window reads nothing
    assert _read(_ctx("pcg_sets")) is None


def test_reads_zero_where_the_plan_keeps_the_scalar_gather(obs):
    lab = dict(variant="jnp", codec="fp16", cache_mode="checkpoint")
    obs.gauge("plan.decode_words", 1000, **lab)
    obs.gauge("plan.window_width", 0, **lab)
    obs.gauge("plan.window_pairs", 0, **lab)
    obs.gauge("plan.residual_words", 0, **lab)
    assert _read(_ctx("spmv_synced")) == 0.0


@pytest.mark.parametrize("config", ["hpcg104_fp16", "hpcg104_e8m"])
def test_reads_the_plan_the_benchmark_builds(obs, config):
    """The configuration's plan at a 16^3 grid (host code: the CPU builds
    the chip's plan) sets the gauges the reader divides."""
    from perfbench import drivers

    cfg = registry.load_config(registry.load_benchmark(), config)
    cfg["matrix"].update(nx=16, ny=16, nz=16)
    op = drivers.build_operator(cfg, lambda msg: None)
    plan = op["plan"]
    resid = int(plan.window[0].shape[0])
    words = recorder.gauge("plan.decode_words")
    assert plan.window_width == recorder.gauge("plan.window_width") > 0
    assert recorder.gauge("plan.residual_words") == resid
    share = _read(_ctx("spmv_synced"))
    assert share == pytest.approx(100 * (1 - resid / words))
    assert 50 < share < 100
