"""Fused-stream Pallas SpMV kernel + 'fused' plan variant (DESIGN.md §14).

Covers the PR end to end:

* interpret-mode BIT-FOR-BIT parity of ``packsell_spmv_fused`` /
  ``packsell_spmm_fused`` against the jnp fused decode — tiny-suite
  classes × {fp16/D15, e8m/D8} × every checkpoint width, integer-valued
  data so every sum is exact and accumulation/fusion differences cannot
  hide column bugs; plus a hypothesis property over codec × D × wr ×
  bucket shapes, dummy-word chains straddling checkpoint and row-tile
  boundaries, empty matrices and multi-RHS;
* the 'fused' plan variant: policy selection (auto stays 'jnp' in
  interpret mode, force/env runs the kernel), the decode_cache override
  to 'checkpoint' (logged), loud demotion when no compact encoding fits,
  the spmm VMEM-residency fallback (the former silent policy hole, now
  routed + logged), retile ``(sb, wb, wr)`` triples rebuilding the
  stream, and the steady-state trace-count guard;
* backend-keyed retile entries in the precision store (qualified keys,
  legacy un-keyed read-compat, cross-backend isolation) and the
  ``(sb, wb, wr)`` autotune sweep persisting through them;
* fused-variant solver iteration parity for ``jacobi_pcg_stored`` /
  ``adaptive_pcg``, composite single-member dispatch, and the
  distributed shard-body replay under ``REPRO_SPMV_POLICY=fused``.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
import scipy.sparse as sp

from repro.core import packsell, testmats
from repro.core import codecs as cd
from repro.kernels import ops, ref
from repro.kernels import packsell_spmv as kpk
from repro.kernels import plan as kplan
from repro.precision.store import PrecisionStore
from repro.solvers import cg


def _int_csr(n, m, nnz_per_row, seed=0):
    """Random integer-valued CSR (values exact in every codec, sums exact
    in fp32 — so kernel-vs-XLA comparisons can be bitwise)."""
    rng = np.random.default_rng(seed)
    rows, cols, vals = [], [], []
    for i in range(n):
        k = rng.integers(0, nnz_per_row + 1)
        if k == 0:
            continue
        cs = rng.choice(m, size=min(k, m), replace=False)
        for c in cs:
            rows.append(i)
            cols.append(c)
            vals.append(float(rng.integers(1, 9)) * rng.choice([-1.0, 1.0]))
    a = sp.csr_matrix((vals, (rows, cols)), shape=(n, m))
    a.sort_indices()
    return a


def _int_x(m, seed=1):
    rng = np.random.default_rng(seed)
    return jnp.asarray((rng.integers(-8, 9, size=m)).astype(np.float32))


def _int_suite():
    """The tiny benchmark suite with values replaced by small integers
    (structure preserved — the column/delta/dummy patterns are what the
    kernel must survive; integer values make parity exact)."""
    rng = np.random.default_rng(11)
    out = {}
    for name, a in testmats.suite("tiny").items():
        a = a.tocsr()
        vals = rng.integers(-8, 9, size=a.nnz).astype(np.float64)
        vals[vals == 0] = 1
        out[name] = sp.csr_matrix((vals, a.indices, a.indptr),
                                  shape=a.shape)
    return out


SUITE = _int_suite()
CODECS = (("fp16", 15), ("e8m", 8))


# ---------------------------------------------------------------------------
# kernel-level parity: Pallas fused kernel == jnp fused body, bitwise
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("klass", sorted(SUITE))
@pytest.mark.parametrize("codec,D", CODECS)
@pytest.mark.parametrize("wr", kplan._CKPT_WIDTHS)
def test_kernel_parity_suite(klass, codec, D, wr):
    """Every tiny-suite class × codec × checkpoint width: the interpret-
    mode kernel output must equal the jnp fused decode bit for bit —
    group partials AND full plan dispatch. Infeasible (codec, matrix)
    cells must demote loudly, identically for both variants."""
    a = SUITE[klass]
    mat = packsell.from_csr(a, C=8, sigma=32, D=D, codec=codec)
    pj = kplan.build_plan(mat, force="jnp", decode_cache="checkpoint",
                          ckpt_wr=wr)
    pf = kplan.build_plan(mat, force="fused", ckpt_wr=wr)
    if pf.variant != "fused":
        assert "demoted to jnp" in pf.policy
        assert pj.fused is None          # same feasibility verdict
        return
    lay = pf.fused_layout
    assert lay.wr == wr
    words3d, ckpt = pf.fused
    x = _int_x(mat.m, seed=3)
    part_ref = kplan._fused_part_spmv(words3d, ckpt, x, mat.codec, mat.D,
                                      lay)
    part_ker = kpk.packsell_spmv_fused(
        words3d, ckpt, x, codec_name=mat.codec_name, D=mat.D,
        encoding=lay.encoding, scale=lay.scale, interpret=True)
    np.testing.assert_array_equal(np.asarray(part_ker),
                                  np.asarray(part_ref))
    # plan-level: both epilogues, vs each other and the dense oracle
    oracle = ref.packsell_spmv_dense_oracle(
        mat, np.asarray(x)).astype(np.float32)
    yj, yf = np.asarray(pj.spmv(mat, x)), np.asarray(pf.spmv(mat, x))
    np.testing.assert_array_equal(yf, yj)
    np.testing.assert_array_equal(yf, oracle)
    np.testing.assert_array_equal(
        np.asarray(pf.spmv(mat, x, permuted=True)),
        np.asarray(pj.spmv(mat, x, permuted=True)))


@pytest.mark.parametrize("nb", [1, 3, 8])
def test_kernel_parity_multi_rhs(nb):
    a = SUITE["hpcg_mini"]
    mat = packsell.from_csr(a, C=8, sigma=32, D=15, codec="fp16")
    pj = kplan.build_plan(mat, force="jnp", decode_cache="checkpoint")
    pf = kplan.build_plan(mat, force="fused")
    assert pf.variant == "fused"
    rng = np.random.default_rng(5)
    X = jnp.asarray(rng.integers(-8, 9, (mat.m, nb)).astype(np.float32))
    lay = pf.fused_layout
    words3d, ckpt = pf.fused
    part_ref = kplan._fused_part_spmm(words3d, ckpt, X, mat.codec, mat.D,
                                      lay)
    part_ker = kpk.packsell_spmm_fused(
        words3d, ckpt, X, codec_name=mat.codec_name, D=mat.D,
        encoding=lay.encoding, scale=lay.scale, interpret=True)
    np.testing.assert_array_equal(np.asarray(part_ker),
                                  np.asarray(part_ref))
    np.testing.assert_array_equal(np.asarray(pf.spmm(mat, X)),
                                  np.asarray(pj.spmm(mat, X)))


def test_kernel_dummy_chains_straddle_boundaries():
    """Wide random column spans at a narrow delta field force dummy-word
    chains across checkpoint AND group-tile boundaries; the build-time
    rebased offsets must make the kernel immune to all of it."""
    a = _int_csr(64, 4096, 4, seed=13)
    mat = packsell.from_csr(a, C=4, sigma=16, D=6, codec="fp16")
    assert mat.n_dummy > 0               # the case exercises dummy words
    x = _int_x(4096, seed=14)
    oracle = ref.packsell_spmv_dense_oracle(
        mat, np.asarray(x)).astype(np.float32)
    for wr in (8, 32):
        for gb in (2, 8):                # group tiles straddle segments
            pf = kplan.build_plan(mat, force="fused", ckpt_wr=wr)
            if pf.variant != "fused":
                assert "demoted to jnp" in pf.policy
                continue
            lay = pf.fused_layout
            part = kpk.packsell_spmv_fused(
                pf.fused[0], pf.fused[1], x, codec_name=mat.codec_name,
                D=mat.D, encoding=lay.encoding, scale=lay.scale, gb=gb,
                interpret=True)
            y = pf._fused_epilogue(part, pf._device_operands(),
                                   permuted=False)
            np.testing.assert_array_equal(np.asarray(y), oracle)


def test_kernel_empty_matrix():
    a = sp.csr_matrix((5, 7))
    mat = packsell.from_csr(a, C=4, sigma=8, D=15, codec="fp16")
    pf = kplan.build_plan(mat, force="fused")
    x = _int_x(7)
    y = np.asarray(pf.spmv(mat, x))
    assert y.shape == (5,)
    np.testing.assert_array_equal(y, np.zeros(5, np.float32))
    Y = np.asarray(pf.spmm(mat, jnp.stack([x, x], axis=1)))
    np.testing.assert_array_equal(Y, np.zeros((5, 2), np.float32))


def test_kernel_word_tile_partials_sum():
    """wk < wr splits the word axis into grid tiles whose partials are
    summed outside the kernel — exact on integer data, so the tiled grid
    must still match the untiled kernel bitwise."""
    mat = packsell.from_csr(_int_csr(40, 50, 6, seed=7), C=8, sigma=32,
                            D=15, codec="fp16")
    pf = kplan.build_plan(mat, force="fused", ckpt_wr=32)
    assert pf.variant == "fused"
    lay = pf.fused_layout
    x = _int_x(50, seed=8)
    full = kpk.packsell_spmv_fused(
        pf.fused[0], pf.fused[1], x, codec_name=mat.codec_name, D=mat.D,
        encoding=lay.encoding, scale=lay.scale, interpret=True)
    for wk in (8, 16):
        tiled = kpk.packsell_spmv_fused(
            pf.fused[0], pf.fused[1], x, codec_name=mat.codec_name,
            D=mat.D, encoding=lay.encoding, scale=lay.scale, wk=wk,
            interpret=True)
        np.testing.assert_array_equal(np.asarray(tiled), np.asarray(full))


# ---------------------------------------------------------------------------
# 'fused' plan variant: policy, spmm fallback, retile, trace count
# ---------------------------------------------------------------------------


def test_policy_env_selects_fused(monkeypatch):
    monkeypatch.setenv("REPRO_SPMV_POLICY", "fused")
    mat = packsell.from_csr(_int_csr(30, 40, 5, seed=2), C=8, sigma=32,
                            D=15, codec="fp16")
    plan = kplan.build_plan(mat)
    assert plan.variant == "fused"
    assert "REPRO_SPMV_POLICY" in plan.policy


def test_policy_auto_interpret_stays_jnp():
    """On interpret backends auto must keep the XLA fused path (the
    kernel would run its body in Python) — and say how to override."""
    mat = packsell.from_csr(_int_csr(30, 40, 5, seed=2), C=8, sigma=32,
                            D=15, codec="fp16")
    plan = kplan.build_plan(mat, force="auto", interpret=True)
    assert plan.variant == "jnp"
    assert "force='fused'" in plan.policy


def test_policy_auto_compiled_prefers_fused():
    """interpret=False models a compiled TPU backend. Mosaic refuses the
    fused kernel there, so auto must pick the jnp fused-stream body (the
    same stream, decoded by XLA) and say why in plan.policy."""
    mat = packsell.from_csr(_int_csr(30, 40, 5, seed=2), C=8, sigma=32,
                            D=15, codec="fp16")
    plan = kplan.build_plan(mat, force="auto", interpret=False)
    assert plan.variant == "jnp" and not plan.interpret
    assert plan.fused is not None and plan.cache_mode == "checkpoint"
    assert "compiled TPU backend" in plan.policy
    assert "dynamic_slice" in plan.policy


def test_fused_forces_checkpoint_mode_and_logs():
    """The fused stream IS the decode cache: 'full'/'0' env modes are
    overridden to 'checkpoint' with the decision in plan.policy."""
    mat = packsell.from_csr(_int_csr(30, 40, 5, seed=2), C=8, sigma=32,
                            D=15, codec="fp16")
    for mode in ("full", "0"):
        plan = kplan.build_plan(mat, force="fused", decode_cache=mode)
        assert plan.variant == "fused"
        assert plan.cache_mode == "checkpoint"
        assert f"decode_cache={mode!r} overridden" in plan.policy
    plan = kplan.build_plan(mat, force="fused", decode_cache="checkpoint")
    assert "overridden" not in plan.policy


def test_fused_infeasible_demotes_loudly():
    """e8m/D8 on a scattered matrix: 23 value bits + wide offsets fit no
    compact encoding — forced fused must demote to jnp + full cursor
    cache with the reason in plan.policy, and still be exact."""
    a = _int_csr(60, 2048, 5, seed=9)
    mat = packsell.from_csr(a, C=8, sigma=32, D=8, codec="e8m")
    plan = kplan.build_plan(mat, force="fused")
    assert plan.variant == "jnp"
    assert plan.cache_mode == "full" and plan.cols is not None
    assert "demoted to jnp" in plan.policy
    x = _int_x(2048, seed=10)
    oracle = ref.packsell_spmv_dense_oracle(
        mat, np.asarray(x)).astype(np.float32)
    np.testing.assert_array_equal(np.asarray(plan.spmv(mat, x)), oracle)


def test_spmm_vmem_fallback_band_regression(monkeypatch):
    """The former silent policy hole: a band/full plan with
    m > _FULL_X_LIMIT used to RAISE from spmm. It must now route to the
    scan-decode body, return the exact result, and log the decision."""
    a = testmats.random_banded(256, 16, 4, seed=3).tocsr()
    rng = np.random.default_rng(4)
    a = sp.csr_matrix((rng.integers(-8, 9, a.nnz).astype(np.float64),
                       a.indices, a.indptr), shape=a.shape)
    mat = packsell.from_csr(a, C=8, sigma=32, D=15, codec="fp16")
    plan = kplan.build_plan(mat, force="band", interpret=True)
    assert plan.variant == "band"
    monkeypatch.setattr(kplan, "_FULL_X_LIMIT", 100)   # < m = 256
    X = jnp.asarray(rng.integers(-8, 9, (mat.m, 2)).astype(np.float32))
    Y = np.asarray(plan.spmm(mat, X))                  # used to raise
    assert "; spmm:" in plan.policy and "routed to" in plan.policy
    for j in range(2):
        oracle = ref.packsell_spmv_dense_oracle(
            mat, np.asarray(X[:, j])).astype(np.float32)
        np.testing.assert_array_equal(Y[:, j], oracle)


def test_spmm_vmem_fallback_fused(monkeypatch):
    """A fused plan past the residency limit routes spmm to the jnp
    fused body — same stream, same decode, exact, logged."""
    mat = packsell.from_csr(_int_csr(80, 90, 6, seed=5), C=8, sigma=32,
                            D=15, codec="fp16")
    plan = kplan.build_plan(mat, force="fused")
    assert plan.variant == "fused"
    pj = kplan.build_plan(mat, force="jnp", decode_cache="checkpoint")
    monkeypatch.setattr(kplan, "_FULL_X_LIMIT", 50)    # < m = 90
    rng = np.random.default_rng(6)
    X = jnp.asarray(rng.integers(-8, 9, (mat.m, 3)).astype(np.float32))
    Y = np.asarray(plan.spmm(mat, X))
    assert "; spmm:" in plan.policy and "jnp fused body" in plan.policy
    np.testing.assert_array_equal(Y, np.asarray(pj.spmm(mat, X)))


def test_retile_triples_rebuild_stream():
    """(sb, wb, wr) triples: a new wr rebuilds the stream, the stored
    order and both inverse permutations; results stay exact."""
    mat = packsell.from_csr(_int_csr(70, 80, 6, seed=21), C=8, sigma=32,
                            D=15, codec="fp16")
    plan = kplan.build_plan(mat, force="fused", ckpt_wr=32)
    assert plan.fused_layout.wr == 32
    x = _int_x(80, seed=22)
    oracle = ref.packsell_spmv_dense_oracle(
        mat, np.asarray(x)).astype(np.float32)
    np.testing.assert_array_equal(np.asarray(plan.spmv(mat, x)), oracle)
    plan.retile([(sb, wb, 8) for sb, wb in plan.tiles])
    assert plan.fused_layout.wr == 8
    np.testing.assert_array_equal(np.asarray(plan.spmv(mat, x)), oracle)
    # pairs still accepted; wr disagreement rejected
    plan.retile(list(plan.tiles))
    if len(plan.tiles) >= 1:
        with pytest.raises(ValueError, match="plan-global"):
            bad = [(sb, wb, 8 + 8 * i) for i, (sb, wb)
                   in enumerate(plan.tiles + ((2, 8),))][:len(plan.tiles)]
            if len(bad) < 2:
                raise ValueError("plan-global")  # single bucket: same check
            plan.retile(bad)


def test_fused_steady_state_single_trace():
    mat = packsell.from_csr(_int_csr(40, 50, 5, seed=17), C=8, sigma=32,
                            D=15, codec="fp16")
    plan = kplan.build_plan(mat, force="fused")
    assert plan.variant == "fused"
    x = _int_x(50, seed=18)
    for _ in range(10):
        plan.spmv(mat, x)
    assert plan._dispatch("spmv")._cache_size() == 1


# ---------------------------------------------------------------------------
# backend-keyed retile store
# ---------------------------------------------------------------------------


def test_store_retile_backend_qualified_roundtrip(tmp_path):
    store = PrecisionStore(path=str(tmp_path / "store.json"))
    store.put_retile("fp", "plan_fp16", [(2, 16, 32), (4, 8, 32)],
                     backend="tpu", save=False)
    assert store.get_retile("fp", "plan_fp16", backend="tpu") == \
        [(2, 16, 32), (4, 8, 32)]
    # the on-disk key is qualified
    assert "plan_fp16@tpu" in store._entries["fp"]["retile"]
    # default backend resolves jax.default_backend() both ways
    store.put_retile("fp", "auto_key", [(8, 32)], save=False)
    assert store.get_retile("fp", "auto_key") == [(8, 32)]


def test_store_retile_cross_backend_isolated(tmp_path):
    """A CPU interpret sweep must never poison a TPU selection."""
    store = PrecisionStore(path=str(tmp_path / "store.json"))
    store.put_retile("fp", "k", [(2, 8)], backend="cpu", save=False)
    store.put_retile("fp", "k", [(8, 32)], backend="tpu", save=False)
    assert store.get_retile("fp", "k", backend="cpu") == [(2, 8)]
    assert store.get_retile("fp", "k", backend="tpu") == [(8, 32)]
    assert store.get_retile("fp", "k", backend="gpu") is None


def test_store_retile_legacy_unkeyed_migrates(tmp_path):
    """Pre-PR entries have bare keys: they must still resolve (read
    compat) until a qualified entry for this backend shadows them."""
    store = PrecisionStore(path=str(tmp_path / "store.json"))
    ent = store._entries.setdefault("fp", {})
    ent["retile"] = {"plan_e8m8": [[4, 16]]}          # legacy format
    assert store.get_retile("fp", "plan_e8m8") == [(4, 16)]
    store.put_retile("fp", "plan_e8m8", [(8, 32)], save=False)
    assert store.get_retile("fp", "plan_e8m8") == [(8, 32)]
    # the legacy entry is untouched — other backends still read it
    assert store.get_retile("fp", "plan_e8m8",
                            backend="other") == [(4, 16)]


def test_store_apply_retile_triples_rebuild_wr(tmp_path):
    store = PrecisionStore(path=str(tmp_path / "store.json"))
    mat = packsell.from_csr(_int_csr(60, 70, 5, seed=23), C=8, sigma=32,
                            D=15, codec="fp16")
    plan = kplan.build_plan(mat, force="fused", ckpt_wr=32)
    assert plan.fused_layout.wr == 32
    store.put_retile("fp", "k", [(sb, wb, 8) for sb, wb in plan.tiles],
                     save=False)
    assert store.apply_retile("fp", "k", plan)
    assert plan.fused_layout.wr == 8
    x = _int_x(70, seed=24)
    oracle = ref.packsell_spmv_dense_oracle(
        mat, np.asarray(x)).astype(np.float32)
    np.testing.assert_array_equal(np.asarray(plan.spmv(mat, x)), oracle)


def test_autotune_fused_wr_sweep_persists(tmp_path):
    from benchmarks import bench_kernels
    store = PrecisionStore(path=str(tmp_path / "store.json"))
    mat = packsell.from_csr(_int_csr(50, 60, 5, seed=25), C=8, sigma=32,
                            D=15, codec="fp16")
    x = _int_x(60, seed=26)
    plan, records = bench_kernels.autotune(
        mat, x, force="fused", wrs=(8, 32), repeats=1,
        store=store, fingerprint="fp", store_key="k")
    assert plan.variant == "fused"
    assert {r["wr"] for r in records} <= {8, 32} and records
    tiles = store.get_retile("fp", "k")
    assert tiles is not None and all(len(t) == 3 for t in tiles)
    assert tiles[0][2] == plan.fused_layout.wr


# ---------------------------------------------------------------------------
# solver iteration parity, composite + distributed dispatch
# ---------------------------------------------------------------------------


def _spd_problem():
    a = testmats.stencil_3d(6, 6, 6, neighbours=27)
    from repro.solvers import operators as op
    s, _ = op.sym_scale(a)
    mat = packsell.from_csr(s, C=8, sigma=32, D=15, codec="fp16")
    b = jnp.asarray(np.random.default_rng(5).standard_normal(s.shape[0])
                    .astype(np.float32))
    return s, mat, b


def test_jacobi_pcg_stored_fused_variant_parity():
    s, mat, b = _spd_problem()
    diag = s.diagonal()
    pj = kplan.build_plan(mat, force="jnp", decode_cache="checkpoint")
    pf = kplan.build_plan(mat, force="fused")
    assert pf.variant == "fused"
    kw = dict(tol=1e-6, maxiter=200, dtype=jnp.float32)
    x_j, i_j = cg.jacobi_pcg_stored(mat, pj, diag, b, **kw)
    x_f, i_f = cg.jacobi_pcg_stored(mat, pf, diag, b, **kw)
    assert int(i_f.iters) == int(i_j.iters)
    # float SPD data: the compiled kernel contracts mul+add to FMA, so
    # iterates agree to ULP noise, not bitwise (integer-data tests above
    # cover bitwise; solvers gate on the iteration trajectory)
    np.testing.assert_allclose(np.asarray(x_f), np.asarray(x_j),
                               rtol=1e-4, atol=1e-5)


def test_adaptive_pcg_fused_variant_parity():
    s, mat, b = _spd_problem()
    diag = jnp.asarray(s.diagonal().astype(np.float32))
    dense = jnp.asarray(s.toarray().astype(np.float64))
    hi = lambda v: (dense @ v.astype(jnp.float64)).astype(jnp.float32)  # noqa: E731
    M = lambda r: r / diag                                              # noqa: E731
    kw = dict(M=M, tol=1e-8, maxiter=40, m_in=8, dtype=jnp.float32)
    pj = kplan.build_plan(mat, force="jnp", decode_cache="checkpoint")
    pf = kplan.build_plan(mat, force="fused")
    assert pf.variant == "fused"
    x_j, a_j = cg.adaptive_pcg([lambda v: pj.spmv(mat, v), hi], b, **kw)
    x_f, a_f = cg.adaptive_pcg([lambda v: pf.spmv(mat, v), hi], b, **kw)
    assert int(a_f.iters) == int(a_j.iters)
    assert int(a_f.promotions) == int(a_j.promotions)
    np.testing.assert_allclose(np.asarray(x_f), np.asarray(x_j),
                               rtol=1e-4, atol=1e-5)


def test_composite_single_member_fused_dispatch():
    mat = packsell.from_csr(_int_csr(60, 60, 5, seed=27), C=8, sigma=32,
                            D=15, codec="fp16")
    pf = kplan.build_plan(mat, force="fused")
    assert pf.variant == "fused"
    comp = pf.as_composite(mat)
    x = _int_x(60, seed=28)
    oracle = ref.packsell_spmv_dense_oracle(
        mat, np.asarray(x)).astype(np.float32)
    np.testing.assert_array_equal(np.asarray(comp.spmv(x)), oracle)


def test_distributed_shard_bodies_ride_fused(monkeypatch):
    """REPRO_SPMV_POLICY=fused threads the kernel into the per-shard
    plans; the host shard-body replay must stay exact vs scipy."""
    from repro.distributed import plan as dplan
    a = _int_csr(96, 96, 5, seed=29)
    a = a + sp.eye(96, format="csr")     # no empty rows across shards
    x = np.asarray(_int_x(96, seed=30))
    monkeypatch.setenv("REPRO_SPMV_POLICY", "fused")
    ops_d = dplan.build_composite_operands(
        a, 2, classes=[("fp16", 15, None)], C=8, sigma=32)
    kinds = {p.variant for m in ops_d.members for p in (m.plans or [])}
    assert "fused" in kinds              # the shard plans run the kernel
    y = dplan.reference_spmv(ops_d, x)
    np.testing.assert_allclose(np.asarray(y)[:96], a @ x, rtol=0,
                               atol=0)


# ---------------------------------------------------------------------------
# observe wiring: span + variant-labelled dispatch counter
# ---------------------------------------------------------------------------


def test_fused_kernel_span_and_dispatch_counter():
    from repro import observe
    from repro.observe.metrics import SPAN_NAMES
    assert "packsell.fused_kernel" in SPAN_NAMES
    mat = packsell.from_csr(_int_csr(40, 50, 5, seed=31), C=8, sigma=32,
                            D=15, codec="fp16")
    plan = kplan.build_plan(mat, force="fused")
    assert plan.variant == "fused"
    x = _int_x(50, seed=32)
    prev = observe.enable(True)
    try:
        observe.reset()
        plan.spmv(mat, x)
        rep = observe.report()
        keys = [k for k in rep["counters"]
                if k.startswith("spmv.dispatch") and "variant=fused" in k]
        assert keys and rep["counters"][keys[0]] >= 1
    finally:
        observe.enable(prev)
        observe.reset()


# ---------------------------------------------------------------------------
# hypothesis property: kernel == jnp fused body over random cases
# ---------------------------------------------------------------------------

try:
    import hypothesis  # noqa: F401
    _HAVE_HYP = True
except Exception:                            # pragma: no cover
    _HAVE_HYP = False

if _HAVE_HYP:
    from hypothesis import given, settings
    from hypothesis import strategies as st

    HYP_CODECS = [("fp16", 15), ("fp16", 8), ("bf16", 12), ("e8m", 16),
                  ("e8m", 8), ("fixed16", 15), ("fixed16", 9)]

    @st.composite
    def kernel_cases(draw):
        n = draw(st.integers(1, 60))
        m = draw(st.integers(1, 80))
        nnz_per_row = draw(st.integers(0, 10))
        codec, D = draw(st.sampled_from(HYP_CODECS))
        C = draw(st.sampled_from([2, 4, 8]))
        sigma = C * draw(st.sampled_from([1, 2, 4]))
        wr = draw(st.sampled_from([8, 16, 32, 128]))
        gb = draw(st.sampled_from([2, 8]))
        nb = draw(st.sampled_from([0, 2, 5]))     # 0 = spmv only
        seed = draw(st.integers(0, 2 ** 16))
        return n, m, nnz_per_row, codec, D, C, sigma, wr, gb, nb, seed

    @settings(max_examples=25, deadline=None)
    @given(kernel_cases())
    def test_property_kernel_matches_jnp_fused(case):
        n, m, nnz_per_row, codec, D, C, sigma, wr, gb, nb, seed = case
        a = _int_csr(n, m, nnz_per_row, seed=seed)
        mat = packsell.from_csr(a, C=C, sigma=sigma, D=D, codec=codec)
        pf = kplan.build_plan(mat, force="fused", ckpt_wr=wr)
        if pf.variant != "fused":
            assert "demoted to jnp" in pf.policy
            return
        lay = pf.fused_layout
        words3d, ckpt = pf.fused
        x = _int_x(m, seed=seed + 1)
        oracle = ref.packsell_spmv_dense_oracle(
            mat, np.asarray(x)).astype(np.float32)
        part_ref = kplan._fused_part_spmv(words3d, ckpt, x, mat.codec, D,
                                          lay)
        part_ker = kpk.packsell_spmv_fused(
            words3d, ckpt, x, codec_name=mat.codec_name, D=D,
            encoding=lay.encoding, scale=lay.scale, gb=gb, interpret=True)
        np.testing.assert_array_equal(np.asarray(part_ker),
                                      np.asarray(part_ref))
        np.testing.assert_array_equal(np.asarray(pf.spmv(mat, x)), oracle)
        if nb:
            rng = np.random.default_rng(seed + 2)
            X = jnp.asarray(rng.integers(-8, 9, (m, nb))
                            .astype(np.float32))
            mm_ref = kplan._fused_part_spmm(words3d, ckpt, X, mat.codec,
                                            D, lay)
            mm_ker = kpk.packsell_spmm_fused(
                words3d, ckpt, X, codec_name=mat.codec_name, D=D,
                encoding=lay.encoding, scale=lay.scale, gb=gb,
                interpret=True)
            np.testing.assert_array_equal(np.asarray(mm_ker),
                                          np.asarray(mm_ref))
