"""Pallas TPU kernel for PackSELL SpMV (paper §4.4, TPU-adapted).

Grid = (slice_blocks, width_blocks). Each kernel instance owns a
``[SB, WB, C]`` VMEM tile of packed words (C = slice size = 128 lanes by
default, SB slices stack on the sublane dimension → word tiles are
VREG-aligned).

Two cursor regimes (DESIGN.md §10.2):

* **legacy carry** (``ckpt=None``) — the column cursor ``c`` and the
  accumulator carry across the width dimension in VMEM scratch (the classic
  reduction-grid pattern): width blocks are a *sequential* carry chain.
* **checkpoint-seeded** (``ckpt=int32[S, nw, C]`` from
  ``plan.py::_build_block_checkpoints``) — each width block seeds its
  cursor from the checkpoint ref instead of the previous block's scratch,
  so width blocks have no data dependence on each other: the width grid
  dimension becomes **parallel**, each block writes its own partial output
  tile and the wrapper reduces over width blocks outside the kernel. No
  cursor scratch, no carry chain.

Unpacking is the paper's branch-free sequence on int32 VREGs (VPU); the MXU
is deliberately unused (SpMV is memory-bound; see DESIGN.md §2).

Two x-delivery variants:

* ``full-x``  — the dense input vector is resident in VMEM (fits for
  n ≲ 1–2M fp32 on a 16 MB VMEM part after tiling the pack stream).
* ``band``    — for RCM/banded matrices (the paper's main regime) only an
  ``XW``-wide window of ``x`` is prefetched per slice-block, selected via a
  scalar-prefetched window id (HBM→VMEM streaming; the GPU kernel gets the
  same effect implicitly through L2).

A third kernel family (:func:`packsell_spmv_fused` / spmm twin) consumes
the plan engine's **fused ragged checkpoint stream** (DESIGN.md §10/§14)
instead of the per-bucket packs: ``uint32[G, wr, C]`` words whose offsets
were prefix-summed at build time and re-based to the per-group ``int32[G,
C]`` checkpoint, so the in-kernel column reconstruction is ONE add per
word (dummy-word chains are already folded into the offsets) and the
group grid axis is embarrassingly parallel. The word decode itself is
:func:`fused_decode_word` — the single definition the jnp fused body
(``plan._fused_decode``) delegates to, so kernel/XLA bit-parity holds by
construction.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import codecs as cd


def _unpack(words: jnp.ndarray, codec: cd.Codec, D: int):
    """Branch-free unpack on uint32 VREGs (paper Fig. 3b)."""
    return cd.unpack_words_jnp(words, codec, D)


def _pad_ckpt(ckpt: jnp.ndarray, s_pad: int) -> jnp.ndarray:
    """Pad the slice axis of a width-block checkpoint (padded slices hold
    PAD words only: any in-range cursor works, 0 is fine)."""
    if s_pad:
        ckpt = jnp.pad(ckpt, ((0, s_pad), (0, 0), (0, 0)))
    return ckpt


# ---------------------------------------------------------------------------
# full-x variant
# ---------------------------------------------------------------------------


def _kernel_full(d0_ref, pack_ref, x_ref, y_ref, c_ref, acc_ref, *,
                 codec_name: str, D: int, nw: int, wb: int):
    codec = cd.make_codec(codec_name)
    wi = pl.program_id(1)

    @pl.when(wi == 0)
    def _init():
        c_ref[...] = jnp.broadcast_to(
            d0_ref[...][:, None], c_ref.shape).astype(jnp.int32)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    c = c_ref[...]
    acc = acc_ref[...]
    pack = pack_ref[...]            # [SB, WB, C] uint32
    x = x_ref[...]                  # [m_pad] f32
    mlim = np.int32(x.shape[0] - 1)

    def body(j, carry):
        c, acc = carry
        v, d = _unpack(pack[:, j, :], codec, D)
        c = c + d.astype(jnp.int32)
        xv = jnp.take(x, jnp.minimum(c, mlim).reshape(-1), axis=0,
                      mode="clip").reshape(c.shape)
        return c, acc + v.astype(jnp.float32) * xv

    c, acc = jax.lax.fori_loop(0, wb, body, (c, acc))
    c_ref[...] = c
    acc_ref[...] = acc

    @pl.when(wi == nw - 1)
    def _fin():
        y_ref[...] = acc


def _kernel_full_ckpt(ckpt_ref, pack_ref, x_ref, y_ref, *,
                      codec_name: str, D: int, wb: int):
    """Checkpoint-seeded full-x kernel: no scratch, no carry — each
    (si, wi) instance is independent and writes its own partial tile."""
    codec = cd.make_codec(codec_name)
    c = ckpt_ref[...].reshape(ckpt_ref.shape[0], ckpt_ref.shape[2])
    pack = pack_ref[...]            # [SB, WB, C] uint32
    x = x_ref[...]                  # [m_pad] f32
    mlim = np.int32(x.shape[0] - 1)
    acc = jnp.zeros(c.shape, jnp.float32)

    def body(j, carry):
        c, acc = carry
        v, d = _unpack(pack[:, j, :], codec, D)
        c = c + d.astype(jnp.int32)
        xv = jnp.take(x, jnp.minimum(c, mlim).reshape(-1), axis=0,
                      mode="clip").reshape(c.shape)
        return c, acc + v.astype(jnp.float32) * xv

    _, acc = jax.lax.fori_loop(0, wb, body, (c, acc))
    y_ref[...] = acc[None]


def packsell_spmv_bucket(pack: jnp.ndarray, d0: jnp.ndarray, x: jnp.ndarray,
                         *, codec_name: str, D: int, sb: int = 8,
                         wb: int = 32, interpret: bool = True,
                         ckpt: jnp.ndarray | None = None) -> jnp.ndarray:
    """Run the full-x kernel over one width bucket. Returns y in stored-row
    order, shape [S, C] float32. Caller applies the σ-permutation gather.

    ``ckpt`` (int32 [S, nw, C], cursor before word ``wi*wb``) switches to
    the checkpoint-seeded kernel: width blocks run grid-parallel and the
    wrapper sums their partial tiles."""
    S, w, C = pack.shape
    s_pad = -S % sb
    w_pad = -w % wb
    if s_pad or w_pad:
        pack = jnp.pad(pack, ((0, s_pad), (0, w_pad), (0, 0)))
        d0 = jnp.pad(d0, (0, s_pad))
    Sp, wp, _ = pack.shape
    m_pad = -x.shape[0] % 128
    xp = jnp.pad(x.astype(jnp.float32), (0, m_pad))
    nw = wp // wb
    grid = (Sp // sb, nw)

    if ckpt is not None:
        kernel = functools.partial(_kernel_full_ckpt, codec_name=codec_name,
                                   D=D, wb=wb)
        y = pl.pallas_call(
            kernel,
            grid=grid,
            in_specs=[
                pl.BlockSpec((sb, 1, C), lambda si, wi: (si, wi, 0)),
                pl.BlockSpec((sb, wb, C), lambda si, wi: (si, wi, 0)),
                pl.BlockSpec((xp.shape[0],), lambda si, wi: (0,)),
            ],
            out_specs=pl.BlockSpec((1, sb, C), lambda si, wi: (wi, si, 0)),
            out_shape=jax.ShapeDtypeStruct((nw, Sp, C), jnp.float32),
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "parallel")),
            interpret=interpret,
            name=f"packsell_spmv_ckpt_{codec_name}_D{D}",
        )(_pad_ckpt(ckpt, s_pad), pack, xp)
        return (y[0] if nw == 1 else jnp.sum(y, axis=0))[:S]

    kernel = functools.partial(_kernel_full, codec_name=codec_name, D=D,
                               nw=nw, wb=wb)
    y = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((sb,), lambda si, wi: (si,)),
            pl.BlockSpec((sb, wb, C), lambda si, wi: (si, wi, 0)),
            pl.BlockSpec((xp.shape[0],), lambda si, wi: (0,)),
        ],
        out_specs=pl.BlockSpec((sb, C), lambda si, wi: (si, 0)),
        out_shape=jax.ShapeDtypeStruct((Sp, C), jnp.float32),
        scratch_shapes=[pltpu.VMEM((sb, C), jnp.int32),
                        pltpu.VMEM((sb, C), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
        name=f"packsell_spmv_{codec_name}_D{D}",
    )(d0, pack, xp)
    return y[:S]


# ---------------------------------------------------------------------------
# band variant
# ---------------------------------------------------------------------------


def _kernel_band(win_ref, d0_ref, pack_ref, xlo_ref, xhi_ref, y_ref, c_ref,
                 acc_ref, *, codec_name: str, D: int, nw: int, wb: int,
                 hw: int):
    """Band variant. The x window is two consecutive half-windows of ``hw``
    elements starting at element ``win[si] * hw`` (delivered as two (1, hw)
    blocks of the same array so the window can slide at half-window
    granularity with plain Blocked indexing); coverage is guaranteed by the
    wrapper when the slice-block's column span fits in ``hw`` elements."""
    codec = cd.make_codec(codec_name)
    si = pl.program_id(0)
    wi = pl.program_id(1)

    @pl.when(wi == 0)
    def _init():
        c_ref[...] = jnp.broadcast_to(
            d0_ref[...][:, None], c_ref.shape).astype(jnp.int32)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    c = c_ref[...]
    acc = acc_ref[...]
    pack = pack_ref[...]
    x = jnp.concatenate([xlo_ref[...].reshape(-1),
                         xhi_ref[...].reshape(-1)])   # [2*hw] window
    base = win_ref[si] * np.int32(hw)
    lim = np.int32(2 * hw - 1)

    def body(j, carry):
        c, acc = carry
        v, d = _unpack(pack[:, j, :], codec, D)
        c = c + d.astype(jnp.int32)
        local = jnp.clip(c - base, 0, lim)
        xv = jnp.take(x, local.reshape(-1), axis=0,
                      mode="clip").reshape(c.shape)
        return c, acc + v.astype(jnp.float32) * xv

    c, acc = jax.lax.fori_loop(0, wb, body, (c, acc))
    c_ref[...] = c
    acc_ref[...] = acc

    @pl.when(wi == nw - 1)
    def _fin():
        y_ref[...] = acc


def _kernel_band_ckpt(win_ref, ckpt_ref, pack_ref, xlo_ref, xhi_ref, y_ref,
                      *, codec_name: str, D: int, wb: int, hw: int):
    """Checkpoint-seeded band kernel: width blocks grid-parallel, partial
    tiles reduced by the wrapper."""
    codec = cd.make_codec(codec_name)
    si = pl.program_id(0)
    c = ckpt_ref[...].reshape(ckpt_ref.shape[0], ckpt_ref.shape[2])
    pack = pack_ref[...]
    x = jnp.concatenate([xlo_ref[...].reshape(-1),
                         xhi_ref[...].reshape(-1)])   # [2*hw] window
    base = win_ref[si] * np.int32(hw)
    lim = np.int32(2 * hw - 1)
    acc = jnp.zeros(c.shape, jnp.float32)

    def body(j, carry):
        c, acc = carry
        v, d = _unpack(pack[:, j, :], codec, D)
        c = c + d.astype(jnp.int32)
        local = jnp.clip(c - base, 0, lim)
        xv = jnp.take(x, local.reshape(-1), axis=0,
                      mode="clip").reshape(c.shape)
        return c, acc + v.astype(jnp.float32) * xv

    _, acc = jax.lax.fori_loop(0, wb, body, (c, acc))
    y_ref[...] = acc[None]


def packsell_spmv_band_bucket(pack: jnp.ndarray, d0: jnp.ndarray,
                              win: jnp.ndarray, x: jnp.ndarray, *,
                              codec_name: str, D: int, hw: int, sb: int = 8,
                              wb: int = 32, interpret: bool = True,
                              ckpt: jnp.ndarray | None = None
                              ) -> jnp.ndarray:
    """Band-windowed variant: ``win[si]`` (scalar-prefetched, so the x DMA
    can be issued ahead of the pack tiles) selects a 2×hw element window of
    x for slice-block ``si``: elements [win*hw, win*hw + 2*hw). The wrapper
    guarantees each slice-block's column span fits within hw, so coverage is
    exact regardless of alignment. ``ckpt`` as in
    :func:`packsell_spmv_bucket`."""
    S, w, C = pack.shape
    s_pad = -S % sb
    w_pad = -w % wb
    if s_pad or w_pad:
        pack = jnp.pad(pack, ((0, s_pad), (0, w_pad), (0, 0)))
        d0 = jnp.pad(d0, (0, s_pad))
    Sp, wp, _ = pack.shape
    # pad x to a whole number of half-windows plus one slack half-window
    x_pad = (-x.shape[0]) % hw + hw
    xp = jnp.pad(x.astype(jnp.float32), (0, x_pad)).reshape(-1, hw)
    nw = wp // wb
    grid = (Sp // sb, nw)

    if ckpt is not None:
        kernel = functools.partial(_kernel_band_ckpt, codec_name=codec_name,
                                   D=D, wb=wb, hw=hw)
        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=grid,
            in_specs=[
                pl.BlockSpec((sb, 1, C), lambda si, wi, win: (si, wi, 0)),
                pl.BlockSpec((sb, wb, C), lambda si, wi, win: (si, wi, 0)),
                pl.BlockSpec((1, hw), lambda si, wi, win: (win[si], 0)),
                pl.BlockSpec((1, hw), lambda si, wi, win: (win[si] + 1, 0)),
            ],
            out_specs=pl.BlockSpec((1, sb, C),
                                   lambda si, wi, win: (wi, si, 0)),
        )
        y = pl.pallas_call(
            kernel,
            grid_spec=grid_spec,
            out_shape=jax.ShapeDtypeStruct((nw, Sp, C), jnp.float32),
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "parallel")),
            interpret=interpret,
            name=f"packsell_spmv_band_ckpt_{codec_name}_D{D}",
        )(win, _pad_ckpt(ckpt, s_pad), pack, xp, xp)
        return (y[0] if nw == 1 else jnp.sum(y, axis=0))[:S]

    kernel = functools.partial(_kernel_band, codec_name=codec_name, D=D,
                               nw=nw, wb=wb, hw=hw)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=grid,
        in_specs=[
            pl.BlockSpec((sb,), lambda si, wi, win: (si,)),
            pl.BlockSpec((sb, wb, C), lambda si, wi, win: (si, wi, 0)),
            pl.BlockSpec((1, hw), lambda si, wi, win: (win[si], 0)),
            pl.BlockSpec((1, hw), lambda si, wi, win: (win[si] + 1, 0)),
        ],
        out_specs=pl.BlockSpec((sb, C), lambda si, wi, win: (si, 0)),
        scratch_shapes=[pltpu.VMEM((sb, C), jnp.int32),
                        pltpu.VMEM((sb, C), jnp.float32)],
    )
    y = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((Sp, C), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
        name=f"packsell_spmv_band_{codec_name}_D{D}",
    )(win, d0, pack, xp, xp)
    return y[:S]


# ---------------------------------------------------------------------------
# multi-RHS variant
# ---------------------------------------------------------------------------


def _kernel_spmm(d0_ref, pack_ref, x_ref, y_ref, c_ref, acc_ref, *,
                 codec_name: str, D: int, nw: int, wb: int):
    """Multi-RHS variant of :func:`_kernel_full`: one walk over the packed
    words feeds all nb right-hand sides (nb× arithmetic intensity — the
    block-Krylov / batched-serving regime)."""
    codec = cd.make_codec(codec_name)
    wi = pl.program_id(1)

    @pl.when(wi == 0)
    def _init():
        c_ref[...] = jnp.broadcast_to(
            d0_ref[...][:, None], c_ref.shape).astype(jnp.int32)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    c = c_ref[...]
    acc = acc_ref[...]
    pack = pack_ref[...]            # [SB, WB, C] uint32
    x = x_ref[...]                  # [m_pad, nb] f32
    mlim = np.int32(x.shape[0] - 1)
    nb = x.shape[1]

    def body(j, carry):
        c, acc = carry
        v, d = _unpack(pack[:, j, :], codec, D)
        c = c + d.astype(jnp.int32)
        xv = jnp.take(x, jnp.minimum(c, mlim).reshape(-1), axis=0,
                      mode="clip").reshape(c.shape + (nb,))
        return c, acc + v.astype(jnp.float32)[..., None] * xv

    c, acc = jax.lax.fori_loop(0, wb, body, (c, acc))
    c_ref[...] = c
    acc_ref[...] = acc

    @pl.when(wi == nw - 1)
    def _fin():
        y_ref[...] = acc


def _kernel_spmm_ckpt(ckpt_ref, pack_ref, x_ref, y_ref, *,
                      codec_name: str, D: int, wb: int):
    codec = cd.make_codec(codec_name)
    c = ckpt_ref[...].reshape(ckpt_ref.shape[0], ckpt_ref.shape[2])
    pack = pack_ref[...]            # [SB, WB, C] uint32
    x = x_ref[...]                  # [m_pad, nb] f32
    mlim = np.int32(x.shape[0] - 1)
    nb = x.shape[1]
    acc = jnp.zeros(c.shape + (nb,), jnp.float32)

    def body(j, carry):
        c, acc = carry
        v, d = _unpack(pack[:, j, :], codec, D)
        c = c + d.astype(jnp.int32)
        xv = jnp.take(x, jnp.minimum(c, mlim).reshape(-1), axis=0,
                      mode="clip").reshape(c.shape + (nb,))
        return c, acc + v.astype(jnp.float32)[..., None] * xv

    _, acc = jax.lax.fori_loop(0, wb, body, (c, acc))
    y_ref[...] = acc[None]


def packsell_spmm_bucket(pack: jnp.ndarray, d0: jnp.ndarray, x: jnp.ndarray,
                         *, codec_name: str, D: int, sb: int = 8,
                         wb: int = 32, interpret: bool = True,
                         ckpt: jnp.ndarray | None = None) -> jnp.ndarray:
    """Run the multi-RHS full-x kernel over one width bucket.

    ``x``: [m, nb]. Returns Y in stored-row order, shape [S, C, nb] float32;
    the caller applies the σ-permutation gather once (plan.py epilogue).
    ``nb`` is padded to a sublane multiple internally; real-TPU deployments
    want nb a multiple of the 128-lane VREG width for full effect.
    ``ckpt`` as in :func:`packsell_spmv_bucket`.
    """
    S, w, C = pack.shape
    nb = x.shape[1]
    s_pad = -S % sb
    w_pad = -w % wb
    if s_pad or w_pad:
        pack = jnp.pad(pack, ((0, s_pad), (0, w_pad), (0, 0)))
        d0 = jnp.pad(d0, (0, s_pad))
    Sp, wp, _ = pack.shape
    m_pad = -x.shape[0] % 128
    nb_pad = -nb % 8
    xp = jnp.pad(x.astype(jnp.float32), ((0, m_pad), (0, nb_pad)))
    nbp = xp.shape[1]
    nw = wp // wb
    grid = (Sp // sb, nw)

    if ckpt is not None:
        kernel = functools.partial(_kernel_spmm_ckpt, codec_name=codec_name,
                                   D=D, wb=wb)
        y = pl.pallas_call(
            kernel,
            grid=grid,
            in_specs=[
                pl.BlockSpec((sb, 1, C), lambda si, wi: (si, wi, 0)),
                pl.BlockSpec((sb, wb, C), lambda si, wi: (si, wi, 0)),
                pl.BlockSpec((xp.shape[0], nbp), lambda si, wi: (0, 0)),
            ],
            out_specs=pl.BlockSpec((1, sb, C, nbp),
                                   lambda si, wi: (wi, si, 0, 0)),
            out_shape=jax.ShapeDtypeStruct((nw, Sp, C, nbp), jnp.float32),
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "parallel")),
            interpret=interpret,
            name=f"packsell_spmm_ckpt_{codec_name}_D{D}",
        )(_pad_ckpt(ckpt, s_pad), pack, xp)
        ys = y[0] if nw == 1 else jnp.sum(y, axis=0)
        return ys[:S, :, :nb]

    kernel = functools.partial(_kernel_spmm, codec_name=codec_name, D=D,
                               nw=nw, wb=wb)
    y = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((sb,), lambda si, wi: (si,)),
            pl.BlockSpec((sb, wb, C), lambda si, wi: (si, wi, 0)),
            pl.BlockSpec((xp.shape[0], nbp), lambda si, wi: (0, 0)),
        ],
        out_specs=pl.BlockSpec((sb, C, nbp), lambda si, wi: (si, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((Sp, C, nbp), jnp.float32),
        scratch_shapes=[pltpu.VMEM((sb, C), jnp.int32),
                        pltpu.VMEM((sb, C, nbp), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
        name=f"packsell_spmm_{codec_name}_D{D}",
    )(d0, pack, xp)
    return y[:S, :, :nb]


# ---------------------------------------------------------------------------
# fused-stream variant (the plan engine's ragged checkpoint operand)
# ---------------------------------------------------------------------------


def fused_decode_word(w: jnp.ndarray, codec: cd.Codec, D: int,
                      encoding: str, scale: float):
    """(value f32, run-local column offset i32) for fused-stream words.

    The ONE decode definition shared by the jnp fused body
    (``plan._fused_decode``) and the Pallas fused kernels below —
    kernel/XLA bit-parity is by construction, not by test luck. The
    16/16 split encodings are two fixed shifts; ``'words'`` is the
    canonical branch-free unpack with the delta field already rewritten
    to the re-based offset."""
    if encoding == "f16":
        v16 = (w >> np.uint32(16)).astype(jnp.uint16)
        v = jax.lax.bitcast_convert_type(v16, jnp.float16)
        local = (w & np.uint32(0xFFFF)).astype(jnp.int32)
    elif encoding == "top16":
        v = jax.lax.bitcast_convert_type(w & np.uint32(0xFFFF0000),
                                         jnp.float32)
        local = (w & np.uint32(0xFFFF)).astype(jnp.int32)
    elif encoding == "fixed16":
        v = (jax.lax.bitcast_convert_type(w, jnp.int32)
             >> np.int32(16)).astype(jnp.float32) * np.float32(scale)
        local = (w & np.uint32(0xFFFF)).astype(jnp.int32)
    else:                           # 'words'
        v, local = cd.unpack_words_jnp(w, codec, D)
        local = local.astype(jnp.int32)
    return v.astype(jnp.float32), local


def _kernel_fused(ckpt_ref, words_ref, x_ref, y_ref, *, codec_name: str,
                  D: int, encoding: str, scale: float, wk: int):
    """Fused-stream SpMV kernel body: checkpoint-seeded, carry-free.

    Each (gi, wi) grid instance owns a ``[GB, WK, C]`` word tile plus the
    matching ``[GB, C]`` checkpoints and reconstructs every column as
    ``ckpt + offset`` (the offsets are build-time prefix sums re-based to
    the checkpoint, so dummy-word chains cost nothing at runtime), then
    runs the unrolled decode → gather → FMA chain over the word axis in
    stream order — the same accumulation order as the jnp fused body."""
    codec = cd.make_codec(codec_name)
    ck = ckpt_ref[...]              # [GB, C] int32
    words = words_ref[...]          # [GB, WK, C] uint32
    x = x_ref[...]                  # [m_pad] f32
    mlim = np.int32(x.shape[0] - 1)
    acc = jnp.zeros(ck.shape, jnp.float32)

    def body(j, acc):
        v, local = fused_decode_word(words[:, j, :], codec, D, encoding,
                                     scale)
        cols = ck + local
        xv = jnp.take(x, jnp.minimum(cols, mlim).reshape(-1), axis=0,
                      mode="clip").reshape(ck.shape)
        return acc + v * xv

    acc = jax.lax.fori_loop(0, wk, body, acc)
    y_ref[...] = acc[None]


def _kernel_fused_mm(ckpt_ref, words_ref, x_ref, y_ref, *, codec_name: str,
                     D: int, encoding: str, scale: float, wk: int):
    """Multi-RHS twin of :func:`_kernel_fused`: one walk over the word
    tile feeds all nb right-hand sides (nb× arithmetic intensity)."""
    codec = cd.make_codec(codec_name)
    ck = ckpt_ref[...]              # [GB, C] int32
    words = words_ref[...]          # [GB, WK, C] uint32
    x = x_ref[...]                  # [m_pad, nb] f32
    mlim = np.int32(x.shape[0] - 1)
    nb = x.shape[1]
    acc = jnp.zeros(ck.shape + (nb,), jnp.float32)

    def body(j, acc):
        v, local = fused_decode_word(words[:, j, :], codec, D, encoding,
                                     scale)
        cols = ck + local
        xv = jnp.take(x, jnp.minimum(cols, mlim).reshape(-1), axis=0,
                      mode="clip").reshape(ck.shape + (nb,))
        return acc + v[..., None] * xv

    acc = jax.lax.fori_loop(0, wk, body, acc)
    y_ref[...] = acc[None]


def packsell_spmv_fused(words3d: jnp.ndarray, ckpt: jnp.ndarray,
                        x: jnp.ndarray, *, codec_name: str, D: int,
                        encoding: str = "words", scale: float = 0.0,
                        gb: int = 8, wk: int | None = None,
                        interpret: bool = True) -> jnp.ndarray:
    """One Pallas kernel over the whole fused word stream: group partials
    ``[G, C]`` float32 in stream order. The caller (the plan engine)
    applies the unrolled level-chain reduction + the 2-D inverse-perm
    gather epilogue (``plan._fused_epilogue``) — static ``FusedSegment``
    metadata, so the chain unrolls inside the same jitted dispatch.

    Grid = (group tiles, word-run tiles): both axes are parallel because
    every word's column offset is re-based to its group checkpoint — no
    cursor carry exists to serialize on. ``wk`` (word-run tile, default
    the full ``wr``) keeps a single word tile per group by default so the
    accumulation order matches the jnp fused body term for term; smaller
    ``wk`` trades that for more grid parallelism (partial tiles summed
    by the wrapper, like the checkpoint-seeded bucket kernels)."""
    G, wr, C = words3d.shape
    if G == 0:
        return jnp.zeros((0, C), jnp.float32)
    wk = wr if wk is None else max(1, min(int(wk), wr))
    g_pad = -G % gb
    w_pad = -wr % wk
    if g_pad or w_pad:
        # PAD groups/words decode to (v=0, offset=0): they gather x[ckpt]
        # and contribute 0, and padded group rows are trimmed below
        words3d = jnp.pad(words3d, ((0, g_pad), (0, w_pad), (0, 0)))
        ckpt = jnp.pad(ckpt, ((0, g_pad), (0, 0)))
    Gp, wrp, _ = words3d.shape
    m_pad = -x.shape[0] % 128
    xp = jnp.pad(x.astype(jnp.float32), (0, m_pad))
    nwk = wrp // wk
    grid = (Gp // gb, nwk)
    kernel = functools.partial(_kernel_fused, codec_name=codec_name, D=D,
                               encoding=encoding, scale=scale, wk=wk)
    y = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((gb, C), lambda gi, wi: (gi, 0)),
            pl.BlockSpec((gb, wk, C), lambda gi, wi: (gi, wi, 0)),
            pl.BlockSpec((xp.shape[0],), lambda gi, wi: (0,)),
        ],
        out_specs=pl.BlockSpec((1, gb, C), lambda gi, wi: (wi, gi, 0)),
        out_shape=jax.ShapeDtypeStruct((nwk, Gp, C), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        interpret=interpret,
        name=f"packsell_spmv_fused_{encoding}_{codec_name}_D{D}",
    )(ckpt, words3d, xp)
    return (y[0] if nwk == 1 else jnp.sum(y, axis=0))[:G]


def packsell_spmm_fused(words3d: jnp.ndarray, ckpt: jnp.ndarray,
                        x: jnp.ndarray, *, codec_name: str, D: int,
                        encoding: str = "words", scale: float = 0.0,
                        gb: int = 8, wk: int | None = None,
                        interpret: bool = True) -> jnp.ndarray:
    """Multi-RHS fused-stream kernel: ``x`` is [m, nb], returns group
    partials [G, C, nb] float32 (epilogue as in
    :func:`packsell_spmv_fused`). ``nb`` is padded to a sublane multiple
    internally; the whole [m, nb] block is VMEM-resident, so the plan
    engine applies the same residency limit as the full-x kernels."""
    G, wr, C = words3d.shape
    nb = x.shape[1]
    if G == 0:
        return jnp.zeros((0, C, nb), jnp.float32)
    wk = wr if wk is None else max(1, min(int(wk), wr))
    g_pad = -G % gb
    w_pad = -wr % wk
    if g_pad or w_pad:
        words3d = jnp.pad(words3d, ((0, g_pad), (0, w_pad), (0, 0)))
        ckpt = jnp.pad(ckpt, ((0, g_pad), (0, 0)))
    Gp, wrp, _ = words3d.shape
    m_pad = -x.shape[0] % 128
    nb_pad = -nb % 8
    xp = jnp.pad(x.astype(jnp.float32), ((0, m_pad), (0, nb_pad)))
    nbp = xp.shape[1]
    nwk = wrp // wk
    grid = (Gp // gb, nwk)
    kernel = functools.partial(_kernel_fused_mm, codec_name=codec_name, D=D,
                               encoding=encoding, scale=scale, wk=wk)
    y = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((gb, C), lambda gi, wi: (gi, 0)),
            pl.BlockSpec((gb, wk, C), lambda gi, wi: (gi, wi, 0)),
            pl.BlockSpec((xp.shape[0], nbp), lambda gi, wi: (0, 0)),
        ],
        out_specs=pl.BlockSpec((1, gb, C, nbp),
                               lambda gi, wi: (wi, gi, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((nwk, Gp, C, nbp), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        interpret=interpret,
        name=f"packsell_spmm_fused_{encoding}_{codec_name}_D{D}",
    )(ckpt, words3d, xp)
    ys = y[0] if nwk == 1 else jnp.sum(y, axis=0)
    return ys[:G, :, :nb]
