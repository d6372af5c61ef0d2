"""The windowed x gather of the jnp decode bodies (DESIGN.md §10.5).

Every case compares the windowed path with the scalar gather it replaces
— the same plan with its window operands dropped, which is what
``execute_with`` runs for a dev dict without ``'window'`` — bit for bit.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
import scipy.sparse as sp

from perfbench.matrices import kronecker
from repro import observe
from repro.core import packsell, testmats
from repro.distributed import build_dist_plan
from repro.kernels import plan as kplan
from repro.kernels import xgather as xg
from repro.solvers import cg


def _x(m, seed=0):
    return jnp.asarray(np.random.default_rng(seed).standard_normal(m)
                       .astype(np.float32))


def _bits(y):
    return np.asarray(y).view(np.uint32)


def _scalar(plan, mat, x, permuted=False):
    """The plan's body with the window operands dropped: the scalar
    gather of x, as the plan built before the window had it."""
    dev = dict(plan._device_operands(), window=None)
    return jax.jit(lambda d, x: plan.execute_with(mat, d, x,
                                                  permuted=permuted))(dev, x)


def _check_plan(mat, plan, seed=1):
    x = _x(mat.m, seed)
    for permuted in (False, True):
        np.testing.assert_array_equal(
            _bits(plan.spmv(mat, x, permuted=permuted)),
            _bits(_scalar(plan, mat, x, permuted)))


def _window(cols, free, W, m):
    """One part's window operands at width W, from host columns."""
    return xg.window_operands(cols, xg.pair_spans(cols, free), W, m)


def _take(x, win, W):
    """One part's windowed gather, its pairs given as ``[P, C]`` (P
    groups of one word): table, row gather and the lane pick (the
    Pallas interpreter runs the Mosaic kernel)."""
    base, off, res = win
    P, C = off.shape
    ops = xg.kernel_operands(base.reshape(P, 1), off.reshape(P, 1, C))
    got = xg.window_gather(x, (res, (ops,)), W, [P], interpret=True)[0]
    return got.reshape(P, C)


# -- HPCG-style stencils ------------------------------------------------------

@pytest.mark.parametrize("side", [16, 24])
@pytest.mark.parametrize("codec,D,mode", [("fp16", 15, "checkpoint"),
                                          ("e8m", 8, "full")],
                         ids=["fp16", "e8m"])
def test_stencil_window_engages_and_matches_scalar_gather(side, codec, D,
                                                          mode):
    a = testmats.stencil_3d(side, side, side)
    mat = packsell.from_csr(a, C=32, sigma=256, D=D, codec=codec)
    plan = kplan.build_plan(mat)
    assert plan.cache_mode == mode
    assert plan.window_width > 0
    # the fused stream is one part; the cursor cache one part per bucket
    shapes = ([plan.fused[0].shape] if mode == "checkpoint"
              else [p.shape for p in mat.packs])
    wres, ops = plan.window
    assert len(ops) == len(shapes)
    for (wbase, woff), (G, wr, C) in zip(ops, shapes):
        Gp = woff.shape[2]
        assert woff.shape[:2] == (wr, C) and wbase.shape == (wr * Gp,)
        assert Gp >= G and Gp % xg.pick_block(G) == 0
    assert wres.shape[0] > 0
    _check_plan(mat, plan)


def test_windowed_take_equals_the_scalar_take_on_every_real_word():
    a = testmats.stencil_3d(16, 16, 16)
    mat = packsell.from_csr(a, C=32, sigma=256, D=15, codec="fp16")
    plan = kplan.build_plan(mat)
    w3, ck = (np.asarray(t) for t in plan.fused)
    cols, free = kplan._fused_gather_columns(w3, ck, mat, plan.fused_layout)
    x = _x(mat.m, 3)
    got = np.asarray(plan._window_gather(x, plan._device_operands(),
                                         [w3.shape])[0])
    want = np.asarray(kplan._take_x(x, jnp.asarray(cols)))
    np.testing.assert_array_equal(got[~free], want[~free])
    assert (~free).sum() == mat.nnz


@pytest.mark.parametrize("W", [32, 64, 128, 256])
def test_pick_kernel_matches_the_jnp_pick(W):
    """The Mosaic lane pick (Pallas interpreter here) against
    ``jnp.take``, on random windows and offsets — including a pair count
    that is no multiple of the kernel block."""
    rng = np.random.default_rng(W)
    G, wr, C, m = 700, 4, 32, 5000
    cols = np.clip(rng.integers(0, m - W, (G, wr, 1))
                   + rng.integers(0, W // 2, (G, wr, C)), 0, m - 1)
    cols[::9] = rng.integers(0, m, (cols[::9].shape))      # residual pairs
    free = np.zeros(cols.shape, bool)
    win = _window(cols.astype(np.int32), free, W, m)
    assert win[2].size > 0
    x = _x(m, 5)
    want = np.asarray(jnp.take(x, jnp.asarray(cols)))
    base, off, res = win
    got = xg.window_gather(x, (res, (xg.kernel_operands(base, off),)), W,
                           [G], interpret=True)[0]
    np.testing.assert_array_equal(_bits(got), _bits(want))


# -- the window operands at the edges of W ------------------------------------

@pytest.mark.parametrize("W", [32, 64, 128, 256])
def test_span_of_exactly_W_is_a_window_and_W_plus_1_is_residual(W):
    C, m = 32, 2000
    S = xg.stride(W, C)
    lane = np.arange(C)
    cols = np.stack([
        96 + lane,                                          # contiguous
        np.where(lane == C - 1, 256 + W - 1, 256 + lane),   # W columns
        np.where(lane == C - 1, 512 + W, 512 + lane),       # W + 1 columns
        np.where(lane == C - 1, 1025 + W - 1, 1025 + lane),  # W, unaligned
    ]).astype(np.int32)
    assert 256 % S == 0 and 1025 % S
    base, off, res = _window(cols, np.zeros(cols.shape, bool), W, m)
    slot = -(-m // S)                                     # first residual row
    assert list(base) == [96 // S, 256 // S, slot, slot + C // S]
    assert off[1, -1] == W - 1
    np.testing.assert_array_equal(off[2], lane)
    np.testing.assert_array_equal(res, np.concatenate([cols[2], cols[3]]))
    x = _x(m, 4)
    got = _take(x, (base, off, res), W)
    np.testing.assert_array_equal(_bits(got), _bits(jnp.take(x, cols)))


def test_all_padding_pairs_and_free_lanes_read_inside_their_window():
    C, m, W = 32, 500, 32
    lane = np.arange(C)
    cols = np.stack([(7 * lane) % m,            # all ±0: any window serves
                     np.where(lane == 5, 480, 12 + lane)]).astype(np.int32)
    free = np.stack([np.ones(C, bool), lane == 5])    # one far ±0 lane
    spans = xg.pair_spans(cols, free)
    assert list(spans[2]) == [0, C - 1]
    base, off, res = xg.window_operands(cols, spans, W, m)
    assert res.size == 0 and list(base) == [0, 12 // xg.stride(W, C)]
    assert int(off.max()) < W
    got = np.asarray(_take(_x(m), (base, off, res), W))
    want = np.asarray(jnp.take(_x(m), cols))
    np.testing.assert_array_equal(got[~free], want[~free])


def test_rows_without_a_real_word_read_what_the_scalar_gather_reads():
    """A stored row whose every word is ±0 sums ±0 products, whose sign
    follows x: its words are pinned to their own columns."""
    free = np.zeros((3, 2, 4), bool)
    free[1] = True                       # slice 1: every lane empty
    free[2, :, 0] = True                 # slice 2: lane 0 empty
    free[0, 1, 3] = True                 # a padding word of a real row
    pinned = kplan._pin_empty_rows(free, None)
    assert not pinned[1].any() and not pinned[2, :, 0].any()
    assert pinned[0, 1, 3] and pinned.sum() == 1


def test_matrix_with_empty_rows_matches_scalar_gather():
    a = testmats.stencil_3d(12, 12, 12).tolil()
    a[::7] = 0                                   # every 7th row empty
    a = a.tocsr()
    a.eliminate_zeros()
    for codec, D in (("fp16", 15), ("e8m", 8)):
        mat = packsell.from_csr(a, C=32, sigma=256, D=D, codec=codec)
        plan = kplan.build_plan(mat)
        assert plan.window_width > 0
        _check_plan(mat, plan)


@pytest.mark.parametrize("shape", [(10, 11, 13), (9, 9, 7)])
def test_n_not_a_multiple_of_128(shape):
    a = testmats.stencil_3d(*shape)
    assert a.shape[0] % 128
    for codec, D in (("fp16", 15), ("e8m", 8)):
        mat = packsell.from_csr(a, C=32, sigma=256, D=D, codec=codec)
        plan = kplan.build_plan(mat)
        assert plan.window_width > 0
        # n is no multiple of σ: the stored order holds σ-padding slots
        assert plan.total_stored > a.shape[0]
        _check_plan(mat, plan)


# -- power-law input: whatever W the model picks ------------------------------

@pytest.mark.parametrize("name", ["powerlaw", "kronecker"])
@pytest.mark.parametrize("mode", ["checkpoint", "full"])
def test_power_law_input_matches_scalar_gather(name, mode):
    if name == "powerlaw":
        a = testmats.powerlaw(3000, mean_deg=8, seed=5)
    else:
        a = kronecker.build({"scale": 11, "edgefactor": 16, "A": 0.57,
                             "B": 0.19, "C": 0.19, "graph_seed": 2})
    mat = packsell.from_csr(a, C=32, sigma=256, D=15, codec="fp16")
    plan = kplan.build_plan(mat, decode_cache=mode)
    assert plan.window_width in (0,) + xg.WINDOW_WIDTHS
    _check_plan(mat, plan)


# -- input with no narrow pairs keeps the scalar gather -----------------------

def _uniform_random(n=2048, k=32, seed=9):
    """Equal row lengths (k distinct random columns each), n a multiple of
    C: no SELL padding, and every pair's lanes read unrelated columns."""
    rng = np.random.default_rng(seed)
    steps = rng.integers(1, n // k, (n, k))
    cols = (rng.integers(0, n, (n, 1)) + np.cumsum(steps, axis=1)) % n
    vals = rng.standard_normal(n * k).astype(np.float32) + 2.0
    a = sp.csr_matrix((vals, (np.repeat(np.arange(n), k), cols.ravel())),
                      shape=(n, n))
    assert (np.diff(a.indptr) == k).all()
    return a


@pytest.fixture
def obs_on():
    prev = observe.enable(True)
    observe.reset()
    yield
    observe.reset()
    observe.enable(prev)


def _gauges(plan, mat):
    lab = (f"{{cache_mode={plan.cache_mode},codec={mat.codec_name},"
           f"variant={plan.variant}}}")
    g = observe.snapshot()["gauges"]
    return [g[name + lab] for name in
            ("plan.window_width", "plan.window_pairs",
             "plan.residual_words", "plan.decode_words")]


def test_uniform_random_rows_keep_the_single_scalar_gather(obs_on):
    a = _uniform_random()
    mat = packsell.from_csr(a, C=32, sigma=256, D=15, codec="fp16")
    plan = kplan.build_plan(mat)
    assert plan.fused_layout.pad_words == 0
    assert plan.window_width == 0 and plan.window is None
    assert _gauges(plan, mat)[:3] == [0, 0, 0]
    x = _x(mat.m)
    hlo = jax.jit(plan._execute, static_argnums=(3,)).lower(
        plan._exec_mat(mat), plan._device_operands(), x, True
    ).compile().as_text()
    gathers = [ln for ln in hlo.splitlines()
               if " gather(" in ln and "packsell.x_gather" in ln]
    assert len(gathers) == 1
    _check_plan(mat, plan)


def test_stencil_gauges_count_window_and_residual_words(obs_on):
    a = testmats.stencil_3d(16, 16, 16)
    mat = packsell.from_csr(a, C=32, sigma=256, D=15, codec="fp16")
    plan = kplan.build_plan(mat)
    W, pairs, resid, words = _gauges(plan, mat)
    lay = plan.fused_layout
    assert W == plan.window_width > 0
    assert pairs == lay.groups * lay.wr
    assert resid == plan.window[0].shape[0]
    assert 0 < resid < 0.8 * words
    assert words == pairs * lay.C
    assert plan.describe()["window_width"] == W


def _spans(span, lo=None):
    span = np.asarray(span, np.int32)
    lo = np.zeros_like(span) if lo is None else np.asarray(lo, np.int32)
    return [(lo, lo, span, span)]


def test_parts_share_one_table_with_residual_slots_in_part_order():
    """Every part reads one table: the residual pairs of a later part
    take the slots after an earlier part's, even a part of one pair."""
    m, C, lane = 100_000, 32, np.arange(32)
    big = (np.arange(4000)[:, None] * 20 + lane).reshape(1000, 4, C)
    big[::7, 1] = (big[::7, 1] * 13) % m             # residual pairs
    small = big[:1, 1:2] + 5                          # one residual pair
    parts = [(big.astype(np.int32), np.zeros(big.shape, bool)),
             (small.astype(np.int32), np.zeros(small.shape, bool))]
    W, win = xg.build_window(parts, m)
    wres, ops = win
    assert W > 0 and len(ops) == 2
    n_big = len(wres) // C - 1
    np.testing.assert_array_equal(wres[-C:], small.reshape(-1))
    S = xg.stride(W, C)
    assert int(ops[1][0][0]) == (-(-m // S) * S + C * n_big) // S
    x = _x(m, 8)
    got = xg.window_gather(x, win, W, [len(big), len(small)],
                           interpret=True)
    for g, (cols, _) in zip(got, parts):
        np.testing.assert_array_equal(_bits(g), _bits(jnp.take(x, cols)))


def test_cost_model_keeps_scalar_gather_when_every_pair_is_wide():
    m = 100_000
    assert xg.pick_window_width(_spans(np.full(1000, 10_000)), 32, m) == 0
    assert xg.pick_window_width(_spans(np.full(1000, 31)), 32, m) > 0
    # a window narrower than a slice's C lanes cannot hold a residual pair
    assert xg.pick_window_width(_spans(np.zeros(1000)), 512, m) == 0
    # pairs that every width holds take the cheapest row per pair; pairs
    # that only the widest holds take it, its rows beating the residual
    cheapest = min(xg.WINDOW_PAIR_NS, key=xg.WINDOW_PAIR_NS.get)
    assert xg.pick_window_width(_spans(np.full(1000, 31)), 32, m) == cheapest
    assert xg.pick_window_width(_spans(np.full(1000, 200)), 32, m) == 256


# -- callers: the PCG, the distributed plan, retile ---------------------------

def test_jacobi_pcg_stored_equals_the_scalar_gather_solve():
    a = testmats.stencil_3d(12, 12, 12)
    mat = packsell.from_csr(a, C=32, sigma=256, D=15, codec="fp16")
    plan = kplan.build_plan(mat)
    assert plan.window_width > 0
    scalar = dataclasses.replace(plan, window=None, window_width=0,
                                 _fns=kplan.LRUDict(), _view=None)
    diag = jnp.asarray(a.diagonal())
    b = jnp.asarray(np.random.default_rng(2).standard_normal(a.shape[0]))
    x1, i1 = cg.jacobi_pcg_stored(mat, plan, diag, b, tol=1e-8, maxiter=60)
    x0, i0 = cg.jacobi_pcg_stored(mat, scalar, diag, b, tol=1e-8,
                                  maxiter=60)
    assert int(i1.iters) == int(i0.iters) > 3
    np.testing.assert_array_equal(np.asarray(x1), np.asarray(x0))


def test_dist_plan_keeps_the_scalar_gather():
    a = testmats.stencil_3d(10, 10, 10)
    dplan = build_dist_plan(a, 1, C=32, sigma=64, D=15, codec="fp16")
    # shape-derived (SPMD-stacked) shard plans carry no window operands
    for dm in dplan.ops.members:
        for p in dm.plans or ():
            assert p.window is None and p.window_width == 0
    mat = packsell.from_csr(a, C=32, sigma=64, D=15, codec="fp16")
    plan = kplan.build_plan(mat)
    assert plan.window_width > 0
    x = _x(a.shape[0], 6)
    np.testing.assert_allclose(np.asarray(dplan.spmv(np.asarray(x))),
                               np.asarray(plan.spmv(mat, x)),
                               rtol=1e-6, atol=1e-6)


def test_retile_rebuilds_the_window_operands():
    a = testmats.stencil_3d(16, 16, 16)
    mat = packsell.from_csr(a, C=32, sigma=256, D=15, codec="fp16")
    plan = kplan.build_plan(mat)
    wr0 = plan.fused_layout.wr
    new_wr = 16 if wr0 != 16 else 8
    plan.retile([(8, 32, new_wr)] * len(plan.tiles))
    assert plan.fused_layout.wr == new_wr
    lay = plan.fused_layout
    (base, off), = plan.window[1]
    Gp = off.shape[2]
    assert Gp >= lay.groups and tuple(off.shape) == (new_wr, lay.C, Gp)
    assert tuple(base.shape) == (new_wr * Gp,)
    _check_plan(mat, plan)
