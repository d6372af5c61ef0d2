"""Quickstart: build a PackSELL matrix, run SpMV three ways, solve a system.

    PYTHONPATH=src python examples/quickstart.py
"""
import jax
import jax.numpy as jnp
import numpy as np

jax.config.update("jax_enable_x64", True)

from repro.core import packsell, sell, testmats            # noqa: E402
from repro.kernels import ops                              # noqa: E402
from repro.launch.compile_cache import use_compile_cache   # noqa: E402
from repro.solvers import precond                          # noqa: E402
from repro.solvers.cg import pcg                           # noqa: E402
from repro.solvers.operators import OperatorSet, sym_scale  # noqa: E402


def main():
    use_compile_cache()
    # 1) a sparse matrix — the HPCG 27-point stencil (paper §5.2 suite)
    a = testmats.hpcg(12, 12, 12)
    n = a.shape[0]
    print(f"matrix: HPCG 12x12x12, n={n}, nnz={a.nnz}")

    # 2) PackSELL with the paper's FP16 embed (W=32, V=16, D=15)
    A = packsell.from_csr(a, C=128, sigma=256, D=15, codec="fp16")
    S = sell.from_csr(a, C=128, sigma=256, value_dtype="float16")
    ms, ss = A.memory_stats(), S.memory_stats()
    print(f"PackSELL bytes: {ms['packsell_bytes']:,}  "
          f"SELL bytes: {ss['sell_bytes']:,}  "
          f"ratio: {ms['packsell_bytes'] / ss['sell_bytes']:.3f} "
          f"(paper lower bound 0.667), dummies: {A.n_dummy}")

    # 3) SpMV: vectorized jnp path vs the cached plan engine (``auto``: the
    #    fused-stream XLA path) vs an fp64 oracle
    x = jnp.asarray(np.random.default_rng(0).standard_normal(n))
    y_jnp = A.spmv(x.astype(jnp.float32))
    y_plan = ops.packsell_spmv(A, x.astype(jnp.float32))
    y_exact = a @ np.asarray(x)
    print(f"jnp vs plan max |Δ|: "
          f"{float(jnp.max(jnp.abs(y_jnp - y_plan))):.2e}")
    rel = np.linalg.norm(np.asarray(y_jnp) - y_exact) / \
        np.linalg.norm(y_exact)
    print(f"fp16-quantized SpMV rel. error vs fp64: {rel:.2e}")

    # 4) the paper's end game: a mixed-precision solve. FP64 PCG with an
    #    approximate inverse applied through *PackSELL E8M14* SpMV.
    a_s, _ = sym_scale(a)
    ops_set = OperatorSet(a_s, C=32, sigma=256)
    A16 = ops_set.matvec("packsell_e8m8")        # E8M14 values (D=8)
    M = precond.neumann_ainv(ops_set.diag(), A16, k=2, dtype=jnp.float32)
    b = jnp.ones((n,), jnp.float64)
    x_sol, info = pcg(ops_set.matvec("fp64"), b, M=M, tol=1e-9,
                      maxiter=500, dtype=jnp.float64)
    print(f"PCG + PackSELL-E8M14 preconditioner: {int(info.iters)} iters, "
          f"relres {float(info.relres):.2e}")


if __name__ == "__main__":
    main()
