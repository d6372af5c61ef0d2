"""Pallas TPU kernel for baseline SELL-C-σ SpMV (paper §3, cuSELL analogue).

Identical tiling to the PackSELL kernel so benchmark deltas isolate the
format difference: SELL moves (value_bytes + 4) per stored element across two
arrays; PackSELL moves 4 bytes from one array.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(val_ref, col_ref, x_ref, y_ref, acc_ref, *, nw: int, wb: int):
    wi = pl.program_id(1)

    @pl.when(wi == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc = acc_ref[...]
    val = val_ref[...]              # [SB, WB, C]
    col = col_ref[...]              # [SB, WB, C] int32
    x = x_ref[...]
    mlim = np.int32(x.shape[0] - 1)

    def body(j, acc):
        v = val[:, j, :].astype(jnp.float32)
        idx = jnp.minimum(col[:, j, :], mlim)
        xv = jnp.take(x, idx.reshape(-1), axis=0).reshape(idx.shape)
        return acc + v * xv

    acc = jax.lax.fori_loop(0, wb, body, acc)
    acc_ref[...] = acc

    @pl.when(wi == nw - 1)
    def _fin():
        y_ref[...] = acc


def sell_spmv_bucket(val: jnp.ndarray, col: jnp.ndarray, x: jnp.ndarray, *,
                     sb: int = 8, wb: int = 32,
                     interpret: bool = True) -> jnp.ndarray:
    S, w, C = val.shape
    s_pad = -S % sb
    w_pad = -w % wb
    if s_pad or w_pad:
        val = jnp.pad(val, ((0, s_pad), (0, w_pad), (0, 0)))
        col = jnp.pad(col, ((0, s_pad), (0, w_pad), (0, 0)))
    Sp, wp, _ = val.shape
    m_pad = -x.shape[0] % 128
    xp = jnp.pad(x.astype(jnp.float32), (0, m_pad))
    nw = wp // wb
    grid = (Sp // sb, nw)

    kernel = functools.partial(_kernel, nw=nw, wb=wb)
    y = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((sb, wb, C), lambda si, wi: (si, wi, 0)),
            pl.BlockSpec((sb, wb, C), lambda si, wi: (si, wi, 0)),
            pl.BlockSpec((xp.shape[0],), lambda si, wi: (0,)),
        ],
        out_specs=pl.BlockSpec((sb, C), lambda si, wi: (si, 0)),
        out_shape=jax.ShapeDtypeStruct((Sp, C), jnp.float32),
        scratch_shapes=[pltpu.VMEM((sb, C), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
        name="sell_spmv",
    )(val, col, xp)
    return y[:S]
