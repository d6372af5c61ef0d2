"""Core PackSELL library: the paper's primary contribution, in JAX.

Public API::

    from repro.core import packsell, sell, sparse, codecs, testmats
    A = packsell.from_csr(csr, C=128, sigma=256, D=15, codec="fp16")
    y = A.spmv(x)                        # vectorized jnp path
    y = kernels.ops.packsell_spmv(A, x)  # cached plan engine
"""
from . import (codecs, delta, packsell, reorder, sell, sparse,  # noqa: F401
               testmats, trisolve)
from .packsell import (PackSELLMatrix, packsell_spmm_jnp,  # noqa: F401
                       packsell_spmv_jnp)
from .sell import SELLMatrix, sell_spmv_jnp  # noqa: F401
from .sparse import CSRMatrix, COOMatrix, csr_from_scipy, coo_from_scipy  # noqa: F401
