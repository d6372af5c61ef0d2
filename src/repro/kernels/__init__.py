"""Pallas TPU kernels for the paper's compute hot-spot (SpMV).

Modules: ``packsell_spmv`` (the paper's kernels, TPU-adapted; single- and
multi-RHS), ``sell_spmv`` (cuSELL-analogue baseline), ``plan`` (the SpMVPlan
execution engine: cached plans, single-dispatch spmv/spmm, fused σ-scatter),
``composite`` (CompositePlan: the block-composition engine shared by plain,
mixed-precision, and distributed SpMV), ``ops`` (thin public wrappers over
the engine), ``ref`` (pure-jnp oracles).
"""
from . import composite, ops, plan, ref  # noqa: F401
from .composite import (CompositeMember, CompositePlan,  # noqa: F401
                        composite_memory_stats, member_from_csr)
