"""Compile the main path for a described TPU v5e (no chip attached).

The plan dispatch and the whole ``jacobi_pcg_stored`` solve are compiled at
the HPCG default local grid (104^3, fp16/D15) for one chip of a described
``v5e:2x2`` topology; nothing runs. The Pallas bodies are compiled too, and
the test pins what Mosaic refuses today: that refusal is why ``auto``
selects the XLA ``jnp`` path on a TPU (``kernels.plan.PALLAS_REFUSAL``).
The one Mosaic kernel of that path, the windowed x gather's lane pick,
compiles inside the dispatch and the solve.

The topology is described inside a fixture, never at import: only one
process may load the TPU compiler library, and the test workers all import
this file.
"""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import packsell, testmats
from repro.kernels import packsell_spmv as pk
from repro.kernels import plan as kplan
from repro.kernels import sell_spmv as sk
from repro.kernels import xgather as xg
from repro.solvers import cg
from repro.solvers import operators as op

SIDE = 104          # HPCG default local grid (hpcg.dat)
HBM_BYTES = 16e9    # one v5e chip


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    """A described chip, with the persistent compile cache off: compiles
    for it are written to the cache but cannot be read back without one."""
    from jax.experimental.compilation_cache import compilation_cache
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)


@pytest.fixture(scope="module")
def hpcg_plan():
    """(mat, plan) for the 104^3 HPCG stencil, fp16/D15, as a TPU builds
    it (``interpret=False``)."""
    s, _ = op.sym_scale(testmats.hpcg(SIDE, SIDE, SIDE))
    mat = packsell.from_csr(s, C=32, sigma=256, D=15, codec="fp16")
    return mat, kplan.build_plan(mat, interpret=False)


def _shapes(tree, sharding):
    return jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(np.shape(a), a.dtype,
                                       sharding=sharding), tree)


def _fits_one_chip(compiled) -> None:
    ma = compiled.memory_analysis()
    total = (ma.argument_size_in_bytes + ma.output_size_in_bytes
             + ma.temp_size_in_bytes)
    assert total < HBM_BYTES, ma


def test_auto_plan_on_tpu_is_compiled_jnp(hpcg_plan):
    _, plan = hpcg_plan
    assert plan.variant == "jnp" and not plan.interpret
    assert plan.cache_mode == "checkpoint" and plan.fused is not None
    assert "dynamic_slice" in plan.policy


def test_spmv_dispatch_compiles_at_hpcg_104(hpcg_plan, one_chip):
    mat, plan = hpcg_plan
    x = jax.ShapeDtypeStruct((mat.m,), jnp.float32, sharding=one_chip)
    compiled = plan._dispatch("spmv").lower(
        _shapes(plan._exec_mat(mat), one_chip),
        _shapes(plan._device_operands(), one_chip), x, False).compile()
    _fits_one_chip(compiled)
    # no Pallas SpMV body: the one Mosaic kernel is the windowed x
    # gather's lane pick, which the plan's cost model engages at 104^3
    assert plan.window_width > 0
    calls = [ln for ln in compiled.as_text().splitlines()
             if 'custom_call_target="tpu_custom_call"' in ln]
    assert len(calls) == 1 and xg.PICK_KERNEL in calls[0]


def test_jacobi_pcg_stored_compiles_at_hpcg_104(hpcg_plan, one_chip):
    mat, plan = hpcg_plan
    b = jax.ShapeDtypeStruct((mat.n,), jnp.float64, sharding=one_chip)
    x0 = jax.ShapeDtypeStruct((plan.total_stored,), jnp.float64,
                              sharding=one_chip)
    fn = cg.stored_solve_fn(plan, b, tol=1e-6, maxiter=5000,
                            dtype=jnp.float64)
    compiled = fn.lower(_shapes(mat, one_chip),
                        _shapes(plan._device_operands(), one_chip),
                        b, b, x0).compile()
    _fits_one_chip(compiled)


def _fused_case(kernel, encoding, codec, D, nb):
    def case(mat, plan, S):
        w3, ck = plan.fused
        x = S((mat.m, nb) if nb else (mat.m,), jnp.float32)
        return (lambda w, c, x: kernel(
            w, c, x, codec_name=codec, D=D, encoding=encoding,
            interpret=False)), (S(w3.shape, w3.dtype), S(ck.shape, ck.dtype),
                                x)
    return case


def _bucket_case(kernel, *, ckpt=False, band=False, nb=0):
    """A bucket-kernel call at a small (Sb, w, C=32) tile shape."""
    def case(mat, plan, S):
        Sb, w, C, m = 16, 64, 32, 4096
        kw = dict(codec_name="fp16", D=15, interpret=False)
        args = [S((Sb, w, C), jnp.uint32),
                S((Sb, 2, C) if ckpt else (Sb,), jnp.int32)]
        if band:
            args.append(S((2,), jnp.int32))
            kw["hw"] = 1024
        args.append(S((m, nb) if nb else (m,), jnp.float32))

        def f(p, d, *rest):
            if ckpt:
                kw["ckpt"] = d
                d = None
            return kernel(p, d, *rest, **kw)
        return f, tuple(args)
    return case


def _sell_case(mat, plan, S):
    Sb, w, C, m = 16, 64, 32, 4096
    return (lambda v, c, x: sk.sell_spmv_bucket(v, c, x, interpret=False),
            (S((Sb, w, C), jnp.float32), S((Sb, w, C), jnp.int32),
             S((m,), jnp.float32)))


#: what Mosaic (JAX 0.9.0) says about each Pallas body: the first refusal
MOSAIC_REFUSALS = {
    "fused_f16": (_fused_case(pk.packsell_spmv_fused, "f16", "fp16", 15, 0),
                  "dynamic_slice"),
    "fused_mm_f16": (_fused_case(pk.packsell_spmm_fused, "f16", "fp16", 15,
                                 8), "dynamic_slice"),
    "fused_words_e8m": (_fused_case(pk.packsell_spmv_fused, "words", "e8m",
                                    8, 0), "dynamic_slice"),
    "full": (_bucket_case(pk.packsell_spmv_bucket), "rank 1 block shapes"),
    "full_ckpt": (_bucket_case(pk.packsell_spmv_bucket, ckpt=True),
                  "divisible by 8 and 128"),
    "band": (_bucket_case(pk.packsell_spmv_band_bucket, band=True),
             "rank 1 block shapes"),
    "spmm": (_bucket_case(pk.packsell_spmm_bucket, nb=8),
             "rank 1 block shapes"),
    "band_ckpt": (_bucket_case(pk.packsell_spmv_band_bucket, ckpt=True,
                               band=True), "divisible by 8 and 128"),
    "spmm_ckpt": (_bucket_case(pk.packsell_spmm_bucket, ckpt=True, nb=8),
                  "divisible by 8 and 128"),
    "sell": (_sell_case, "dynamic_slice"),
}


@pytest.mark.parametrize("name", sorted(MOSAIC_REFUSALS))
def test_mosaic_refuses_pallas_body(name, hpcg_plan, one_chip):
    """Pins Mosaic's refusal of each Pallas body. When this fails because
    a body compiles, the TPU can run it: revisit ``auto`` and
    ``PALLAS_REFUSAL``. (With ``jax_enable_x64`` on, as the test suite
    runs, JAX 0.9.0 hits a RecursionError before Mosaic gets to say
    anything, so the kernels are traced with x64 off.)"""
    mat, plan = hpcg_plan
    build, needle = MOSAIC_REFUSALS[name]

    def S(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    f, args = build(mat, plan, S)
    with jax.enable_x64(False), pytest.raises(Exception, match=needle):
        jax.jit(f).lower(*args).compile()


@pytest.mark.parametrize("force", ["fused", "full", "band"])
def test_forced_pallas_variant_raises_at_build(force):
    mat = packsell.from_csr(testmats.hpcg(6, 6, 6), C=8, sigma=32, D=15,
                            codec="fp16")
    with pytest.raises(ValueError, match="Mosaic refuses") as e:
        kplan.build_plan(mat, force=force, interpret=False)
    assert "dynamic_slice" in str(e.value)
    assert kplan.build_plan(mat, force=force, interpret=True).variant == force
