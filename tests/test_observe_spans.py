"""The program's span vocabulary, its device scopes, host spans and plan
gauges (DESIGN.md §12.2-12.3).

* **one vocabulary** — every ``span``/``host_span`` name the program plants
  and every scope a benchmark reader looks for is in
  ``metrics.SPAN_NAMES``;
* **device scopes** — the x gather of each decode path sits under
  ``packsell.x_gather`` inside its decode scope, and every instruction of
  the stored PCG loop body is under an SpMV scope, ``packsell.solver_vec``
  or ``packsell.stored_permute``; the scopes change no compiled
  instruction, only its metadata;
* **host spans** — ``host_span`` records ``span_s`` when the recorder is
  on and is a shared null context when it is off; packing, plan building
  and each dispatch record their spans;
* **plan gauges** — set once per plan, equal to the matrix's counts.
"""
from __future__ import annotations

import glob
import os
import re
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import observe
from repro.core import packsell, testmats
from repro.kernels import plan as kplan
from repro.observe import metrics
from repro.solvers import cg

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SPMV_SCOPES = ("packsell.fused_decode", "packsell.fused_kernel",
               "packsell.bucket_decode", "packsell.gather_epilogue")


@pytest.fixture
def obs_on():
    prev = observe.enable(True)
    observe.reset()
    yield
    observe.reset()
    observe.enable(prev)


def _mat(codec="fp16", D=15, side=6):
    a = testmats.stencil_3d(side, side, side)
    return a, packsell.from_csr(a, C=8, sigma=32, D=D, codec=codec)


def _x(m, seed=0):
    return jnp.asarray(
        np.random.default_rng(seed).standard_normal(m).astype(np.float32))


# -- HLO parsing --------------------------------------------------------------

_COMP = re.compile(r"^(?:ENTRY )?%?([\w.\-]+) .*\{$")
_INSTR = re.compile(r"^\s*(?:ROOT )?%?([\w.\-]+) = .*?\s([a-z][\w\-]*)\(")
_OP_NAME = re.compile(r'op_name="([^"]*)"')


def _computations(text: str) -> dict:
    """``{computation name: [instruction lines]}`` of an HLO module."""
    comps, cur = {}, None
    for line in text.splitlines():
        m = _COMP.match(line)
        if m and not line.startswith(" "):
            cur = comps.setdefault(m.group(1), [])
        elif cur is not None and line.startswith("  "):
            cur.append(line)
    return comps


def _scopes(line: str):
    """The ``packsell.*`` scope path of an instruction, or None where it
    carries no ``op_name``."""
    m = _OP_NAME.search(line)
    if m is None:
        return None
    return [c for c in m.group(1).split("/") if c.startswith("packsell.")]


def _strip_metadata(text: str) -> str:
    """HLO text without debug metadata: the ``metadata={...}`` of each
    instruction and the stack-frame tables."""
    text = re.sub(r",? ?metadata=\{[^}]*\}", "", text)
    out, skip = [], False
    for line in text.splitlines():
        if line in ("FileNames", "FunctionNames", "FileLocations",
                    "StackFrames"):
            skip = True
        elif skip and not line.strip():
            skip = False
        elif not skip:
            out.append(line)
    return "\n".join(out)


def _spmv_hlo(codec, D, decode_cache):
    _, mat = _mat(codec, D, side=6)
    plan = kplan.build_plan(mat, decode_cache=decode_cache)
    return plan, plan._dispatch("spmv").lower(
        plan._exec_mat(mat), plan._device_operands(), _x(mat.m),
        False).compile().as_text()


def _pcg_hlo(maxiter=5):
    a, mat = _mat()
    plan = kplan.build_plan(mat)
    b = jnp.ones((mat.n,), jnp.float64)
    fn = cg.stored_solve_fn(plan, b, tol=0.0, maxiter=maxiter,
                            dtype=jnp.float64)
    x0 = jnp.zeros((plan.total_stored,), jnp.float64)
    return fn.lower(mat, plan._device_operands(),
                    jnp.asarray(a.diagonal()), b, x0).compile().as_text()


# -- one vocabulary -----------------------------------------------------------

def test_every_span_the_program_plants_is_in_the_vocabulary():
    call = re.compile(r"\b(?:span|host_span|host_span_handle)\(\s*"
                      r"\"([^\"]+)\"")
    found = set()
    for path in glob.glob(os.path.join(REPO, "src", "**", "*.py"),
                          recursive=True):
        with open(path) as f:
            found |= set(call.findall(f.read()))
    assert len(found) >= 15
    assert found <= set(metrics.SPAN_NAMES), found - set(metrics.SPAN_NAMES)
    assert len(set(metrics.SPAN_NAMES)) == len(metrics.SPAN_NAMES)
    # the vocabulary lives in the recorder alone
    assert not os.path.exists(os.path.join(REPO, "src", "repro", "observe",
                                           "profile.py"))


def test_every_scope_a_benchmark_reader_reads_is_in_the_vocabulary():
    literal = re.compile(r"\"(packsell\.[\w.]+)\"")
    found = set()
    for path in glob.glob(os.path.join(REPO, "perfbench", "metrics",
                                       "*.py")):
        with open(path) as f:
            found |= set(literal.findall(f.read()))
    assert {"packsell.x_gather", "packsell.solver_vec",
            "packsell.stored_permute", "packsell.pack",
            "packsell.plan_build", "packsell.dispatch"} <= found
    assert found <= set(metrics.SPAN_NAMES), found - set(metrics.SPAN_NAMES)


# -- device scopes ------------------------------------------------------------

@pytest.mark.parametrize("codec,D,decode_cache,scope", [
    ("fp16", 15, "checkpoint", "packsell.fused_decode"),
    ("e8m", 8, "full", "packsell.bucket_decode"),
], ids=["fused", "cursor"])
def test_x_gather_scope_nests_in_the_decode_scope(obs_on, codec, D,
                                                   decode_cache, scope):
    plan, text = _spmv_hlo(codec, D, decode_cache)
    assert plan.cache_mode == decode_cache
    gathers = [line for line in text.splitlines()
               if " gather(" in line and scope in (_scopes(line) or [])]
    assert gathers
    for line in gathers:
        path = _scopes(line)
        assert "packsell.x_gather" in path, line
        assert path.index(scope) < path.index("packsell.x_gather")
    # the epilogue's gathers are not the x gather
    assert not any("packsell.x_gather" in (_scopes(line) or [])
                   and "packsell.gather_epilogue" in (_scopes(line) or [])
                   for line in text.splitlines())


def test_stored_pcg_loop_body_is_scoped_apart_from_loop_control(obs_on):
    text = _pcg_hlo()
    comps = _computations(text)
    # the solver's loop (off a TPU the interpreted lane-pick kernel of the
    # windowed x gather runs as loops of its own, inside the SpMV scopes)
    loops = re.findall(r' while\(.*?body=%?([\w.\-]+).*op_name="[^"]*'
                       r'packsell\.solver_while/while"', text)
    assert len(loops) == 1
    body = comps[loops[0]]
    structural = {"parameter", "get-tuple-element", "tuple", "constant",
                  "copy", "bitcast"}
    named = 0
    for line in body:
        path = _scopes(line)
        m = _INSTR.match(line)
        op = m.group(2) if m else ""
        if path is None:
            # made by the compiler: loop-carried plumbing, or a fusion of
            # instructions that carry no op_name either (XLA's rewrite of
            # a reduction)
            called = re.search(r"calls=%?([\w.\-]+)", line)
            assert op in structural or (
                op == "fusion" and called and not any(
                    _OP_NAME.search(x) for x in comps[called.group(1)])), \
                line
            continue
        named += 1
        assert "packsell.solver_while" in path, line
        kinds = [any(s in path for s in SPMV_SCOPES),
                 "packsell.solver_vec" in path,
                 "packsell.stored_permute" in path]
        # exactly one: the scopes never nest in one another
        assert sum(kinds) == 1, line
    assert named >= 5
    # set-up and finish outside the loop: the σ-permutes and the vector
    # work have their own scopes there too
    outside = [line for name, lines in comps.items() if name != loops[0]
               for line in lines if "packsell.solver_while" not in line]
    assert any("packsell.stored_permute" in line for line in outside)
    assert any("packsell.solver_vec" in line for line in outside)


@pytest.mark.parametrize("which", ["spmv_fused", "spmv_cursor", "pcg"])
def test_scopes_change_no_compiled_instruction(which):
    """A program traced with the recorder on (scopes planted) compiles to
    the same instructions as with it off; only the metadata differs.
    Each side builds its own plan, so no jit cache carries a trace over."""
    def build():
        if which == "pcg":
            return _pcg_hlo()
        if which == "spmv_fused":
            return _spmv_hlo("fp16", 15, "checkpoint")[1]
        return _spmv_hlo("e8m", 8, "full")[1]

    prev = observe.enable(False)
    try:
        off = build()
        observe.enable(True)
        on = build()
    finally:
        observe.enable(prev)
        observe.reset()
    assert "packsell." not in off and "packsell." in on
    assert _strip_metadata(off) == _strip_metadata(on)


_CACHED_SCOPES = """
import jax, jax.numpy as jnp
from repro.launch.compile_cache import use_compile_cache
from repro.observe import metrics
use_compile_cache()
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
metrics.enable(True)

def make(name):
    def f(x):
        with metrics.span(name):
            return jnp.sin(x) * 2.0
    return jax.jit(f)

x = jnp.ones((8,))
for name in ("packsell.x_gather", "packsell.solver_vec"):
    print(name, name in make(name).lower(x).compile().as_text())
"""


def test_a_cached_program_keeps_its_own_scopes(tmp_path):
    """Two programs that differ only in their scopes get two entries of the
    persistent compile cache (``use_compile_cache`` keys it on metadata):
    the second does not run with the first one's op_names. A process of
    its own, so no cache reaches the other tests."""
    import subprocess
    import sys

    env = dict(os.environ, JAX_COMPILATION_CACHE_DIR=str(tmp_path),
               JAX_PLATFORMS="cpu",
               PYTHONPATH=os.path.join(REPO, "src"))
    out = subprocess.run([sys.executable, "-c", _CACHED_SCOPES], env=env,
                         capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stderr[-2000:]
    assert out.stdout.split() == ["packsell.x_gather", "True",
                                  "packsell.solver_vec", "True"]
    assert len(os.listdir(tmp_path)) >= 2


# -- host spans ---------------------------------------------------------------

def test_host_span_records_span_s_when_on(obs_on):
    with observe.host_span("packsell.pack", stage="test"):
        time.sleep(0.002)
    handle = observe.host_span_handle("packsell.dispatch", kind="spmv")
    for _ in range(3):
        with handle():
            pass
    h = observe.snapshot()["histograms"]
    one = h["span_s{span=packsell.pack,stage=test}"]
    assert one["count"] == 1 and one["sum"] >= 0.002
    assert h["span_s{kind=spmv,span=packsell.dispatch}"]["count"] == 3


def test_host_span_off_is_a_predicate(monkeypatch):
    class Refuse:
        def __init__(self, *a, **k):
            raise AssertionError("no annotation while the recorder is off")

        @staticmethod
        def is_enabled():
            raise AssertionError("no profiler query while off")

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", Refuse)
    prev = observe.enable(False)
    try:
        observe.reset()
        handle = observe.host_span_handle("packsell.dispatch", kind="spmv")
        assert observe.host_span("packsell.pack") is metrics._NULL_SPAN
        assert handle() is metrics._NULL_SPAN
        with observe.host_span("packsell.pack"), handle():
            pass
        assert observe.snapshot()["histograms"] == {}
    finally:
        observe.enable(prev)


def test_host_span_annotates_only_while_a_profiler_collects(obs_on,
                                                           monkeypatch):
    made = []

    class Fake:
        collecting = False

        def __init__(self, name):
            made.append(name)

        def __enter__(self):
            return self

        def __exit__(self, *exc):
            return False

        @classmethod
        def is_enabled(cls):
            return cls.collecting

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", Fake)
    handle = observe.host_span_handle("packsell.dispatch", kind="spmv")
    with handle(), observe.host_span("packsell.pack"):
        pass
    assert made == []
    Fake.collecting = True
    with handle(), observe.host_span("packsell.pack"):
        pass
    assert made == ["packsell.dispatch", "packsell.pack"]
    assert observe.snapshot()["histograms"][
        "span_s{kind=spmv,span=packsell.dispatch}"]["count"] == 2


def test_pack_and_plan_build_record_their_stages(obs_on):
    a, mat = _mat()
    plan = kplan.build_plan(mat)
    h = observe.snapshot()["histograms"]
    stages = ("packsell.pack", "packsell.pack.encode", "packsell.pack.words",
              "packsell.pack.slices", "packsell.pack.to_device",
              "packsell.plan_build", "packsell.plan_build.stream",
              "packsell.plan_build.inverse",
              "packsell.plan_build.to_device")
    for s in stages:
        assert h[f"span_s{{span={s}}}"]["count"] == 1, s
    # the span replaced the ad-hoc build histogram
    assert not any(k.startswith("plan.build_s") for k in h)
    parts = sum(h[f"span_s{{span={s}}}"]["sum"] for s in stages[1:5])
    assert parts <= h["span_s{span=packsell.pack}"]["sum"]
    assert plan.fused is not None
    kplan.build_plan(mat, decode_cache="full")
    h = observe.snapshot()["histograms"]
    assert h["span_s{span=packsell.plan_build.cache}"]["count"] == 1


@pytest.mark.parametrize("kind", ["spmv", "spmm"])
def test_each_dispatch_records_one_host_span(obs_on, kind):
    _, mat = _mat()
    plan = kplan.build_plan(mat)
    x = _x(mat.m) if kind == "spmv" else jnp.stack([_x(mat.m, s)
                                                     for s in (1, 2)], 1)
    call = plan.spmv if kind == "spmv" else plan.spmm
    for _ in range(4):
        jax.block_until_ready(call(mat, x))
    snap = observe.snapshot()
    h = snap["histograms"][f"span_s{{kind={kind},span=packsell.dispatch}}"]
    assert h["count"] == 4 and h["min"] > 0
    assert sum(v for k, v in snap["counters"].items()
               if k.startswith("spmv.dispatch{")) == 4


def test_stored_pcg_records_one_dispatch_span_per_solve(obs_on):
    a, mat = _mat()
    plan = kplan.get_plan(mat)
    b = jnp.asarray(np.random.default_rng(3).standard_normal(mat.n))
    for _ in range(2):
        cg.jacobi_pcg_stored(mat, plan, a.diagonal(), b, tol=0.0,
                             maxiter=3)
    h = observe.snapshot()["histograms"]
    assert h["span_s{kind=pcg,span=packsell.dispatch}"]["count"] == 2


# -- plan gauges --------------------------------------------------------------

@pytest.mark.parametrize("codec,D,decode_cache", [
    ("fp16", 15, "checkpoint"), ("e8m", 8, "full")], ids=["fp16", "e8m"])
def test_plan_gauges_equal_the_matrix_counts(obs_on, codec, D,
                                             decode_cache):
    _, mat = _mat(codec, D, side=20)
    plan = kplan.build_plan(mat, decode_cache=decode_cache)
    lab = (f"{{cache_mode={plan.cache_mode},codec={codec},"
           f"variant={plan.variant}}}")
    g = observe.snapshot()["gauges"]
    assert g["plan.nnz" + lab] == mat.nnz
    assert g["plan.dummies" + lab] == mat.n_dummy
    if codec == "e8m":
        # D = 8 cannot span a plane of the 20^3 grid in one word
        assert mat.n_dummy > 0
        assert g["plan.decode_words" + lab] == mat.words_bucketed
        assert g["plan.cache_words" + lab] == mat.words_bucketed
    else:
        lay = plan.fused_layout
        assert g["plan.decode_words" + lab] == lay.groups * lay.wr * lay.C
        assert g["plan.cache_words" + lab] == lay.groups * lay.C
    # gauges describe the plan, not traffic: dispatches leave them alone
    before = dict(g)
    jax.block_until_ready(plan.spmv(mat, _x(mat.m)))
    after = observe.snapshot()["gauges"]
    assert {k: after[k] for k in before} == before
