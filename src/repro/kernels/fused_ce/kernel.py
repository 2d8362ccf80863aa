"""Pallas TPU kernels for fused output projection + cross-entropy.

TPU adaptation of the paper's CUDA design (DESIGN.md §2):

  * the logits tile `z = H_tile @ W_tile^T` exists only in VMEM/VREGs —
    the (N, V) logits tensor is never written to HBM;
  * the online-softmax state (m, a) plus the auxiliary sums (z_target,
    z_sum) live in f32 VMEM scratch, carried across the *innermost,
    sequential* vocab grid axis ("arbitrary" dimension semantics);
  * the MXU computes the tile GEMM while the VPU performs the
    max/exp/accumulate updates — the TPU analogue of the paper's
    CUDA-core/Tensor-core overlap;
  * backward is TWO passes (no TPU atomics): a dH kernel accumulating over
    vocab tiles for fixed row tiles, and a dW kernel accumulating over row
    tiles for fixed vocab tiles.  Both recompute the logit tile (paper
    Alg. 2 "logit recompute").

Grid layouts (R = n_rows/bm, Vb = V_padded/bv):

  forward : grid=(R, Vb)  — vocab innermost, state scratch per row tile
  dH      : grid=(R, Vb)  — vocab innermost, dH output block per row tile
  dW      : grid=(Vb, R)  — rows  innermost, dW output block per vocab tile

Gradient filtering (DESIGN.md §9): `fwd_stats(..., return_tile_stats=
True)` additionally emits a per-(row-block, vocab-block) max-valid-logit
statistic from the same online scan; `bwd_grads(..., tile_stats=...)`
with `cfg.grad_filter_eps > 0` derives a sound skip mask from it
(`core/filtering.py`) and runs the kernels' filtered variants, which
gate each tile's recompute + MXU accumulate on the mask, scalar-
prefetched into SMEM.  Without a mask the exact kernels run, bit-for-bit
the pre-filter code.

Layouts follow Mosaic's tiling rule (a block's last two dims divide by
(8, 128) or span the array): the tile statistic of one row block is a
(1, num_v) lane vector resident across the vocab axis, and every kernel
asks for the scoped VMEM its working set needs (`core/windows.py`).
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.types import LossConfig
from repro.core.windows import (BlockPlan, bwd_tile_bytes,
                                choose_blocks, tile_bytes)
from repro.kernels.pallas_utils import compiler_params, interpret_default

_NEG_INF = float("-inf")


def _mxu_dot(a, b, contract):
    """`a . b` on the MXU with f32 accumulation, contracting dims
    `contract` = (a's, b's).  Mosaic's default runs an f32 operand as one
    bf16 pass (8 significant bits, truncated), so a dot with an f32
    operand asks for the f32 (HIGHEST) matmul; bf16 operands take one
    pass."""
    precision = (jax.lax.Precision.HIGHEST
                 if jnp.float32 in (a.dtype, b.dtype) else None)
    return jax.lax.dot_general(
        a, b, dimension_numbers=(contract, ((), ())), precision=precision,
        preferred_element_type=jnp.float32)


def _grad_dot(g, x, contract):
    """The backward's f32 gradient tile `g` against the input tile `x`,
    with `g` rounded to `x`'s dtype first: one bf16 pass for a bf16
    model (its dH and dW are cast to bf16 anyway), the f32 matmul for
    an f32 one."""
    return _mxu_dot(g.astype(x.dtype), x, contract)


def _tile_logits(h_tile, w_tile, cfg: LossConfig, scale_row=None):
    """(bm, bv) logits tile on the MXU, f32 accumulate; softcap applied.

    `scale_row` ((1, bv) f32) marks `w_tile` as row-quantized (int8/fp8,
    `kernels/quant.quantize_weight`): the 1-byte tile is cast in-register
    (lossless — the quantized grids are exact in bf16/f32) and the logits
    tile rescaled BEFORE the softcap, since per-row scales factor out of
    the d-contraction: z[r, v] = s[v] * sum_d h[r, d] * q[v, d].
    """
    if scale_row is not None:
        w_tile = w_tile.astype(h_tile.dtype)
    z = _mxu_dot(h_tile, w_tile, ((1,), (1,)))
    if scale_row is not None:
        z = z * scale_row
    if cfg.logit_softcap is not None:
        cap = jnp.float32(cfg.logit_softcap)
        z = cap * jnp.tanh(z / cap)
    return z


# ---------------------------------------------------------------------------
# Forward kernel
# ---------------------------------------------------------------------------


def _fwd_kernel(off_ref, y_ref, h_ref, w_ref,   # inputs (+ opt. scale)
                *rest,                          # outputs, then scratch
                cfg: LossConfig, valid: int, v_orig: int, bv: int,
                num_v: int, n_orig: int = 0, emit_stats: bool = False,
                quantized: bool = False):
    # variadic tail: [ws_ref (quantized),] lse, ztgt, zsum,
    # [tmax (emit_stats),] m_sc, a_sc, zt_sc, zs_sc — pallas_call passes
    # inputs, then outputs, then scratch, so unpack front-to-back here.
    if quantized:
        ws_ref, *rest = rest
    else:
        ws_ref = None
    lse_ref, ztgt_ref, zsum_ref, *rest = rest
    tmax_ref = None
    if emit_stats:
        tmax_ref, *rest = rest
    m_sc, a_sc, zt_sc, zs_sc = rest
    v = pl.program_id(1)

    @pl.when(v == 0)
    def _init():
        m_sc[...] = jnp.full_like(m_sc[...], _NEG_INF)
        a_sc[...] = jnp.zeros_like(a_sc[...])
        zt_sc[...] = jnp.zeros_like(zt_sc[...])
        zs_sc[...] = jnp.zeros_like(zs_sc[...])

    scale_row = ws_ref[...] if quantized else None
    z = _tile_logits(h_ref[...], w_ref[...], cfg, scale_row)  # (bm, bv) f32
    bm = z.shape[0]
    local_col = v * bv + jax.lax.broadcasted_iota(jnp.int32, (bm, bv), 1)
    col = local_col + off_ref[0, 0]                         # global vocab id
    col_valid = (local_col < v_orig) & (col < valid)
    z = jnp.where(col_valid, z, _NEG_INF)

    # online max / accumulator update (paper Alg. 1 lines 8-14)
    m_prev = m_sc[...]                                      # (bm, 1)
    m_new = jnp.maximum(m_prev, jnp.max(z, axis=1, keepdims=True))
    safe_m = jnp.where(jnp.isneginf(m_new), 0.0, m_new)
    a_sc[...] = (a_sc[...] * jnp.exp(m_prev - safe_m)
                 + jnp.sum(jnp.exp(z - safe_m), axis=1, keepdims=True))
    m_sc[...] = m_new

    # target logit (line 15-16) and valid-logit sum (label smoothing);
    # col_valid guard: local pad columns alias other shards' global ids
    y = y_ref[...]                                          # (bm, 1) int32
    zt_sc[...] += jnp.sum(jnp.where((col == y) & col_valid, z, 0.0),
                          axis=1, keepdims=True)
    zs_sc[...] += jnp.sum(jnp.where(col_valid, z, 0.0), axis=1, keepdims=True)

    if emit_stats:
        # grad-filter statistic (DESIGN.md §9): tile max over live rows —
        # pad rows (>= n_orig) and ignore-masked rows are excluded so the
        # backward's skip mask never depends on dead rows
        row = pl.program_id(0) * bm + jax.lax.broadcasted_iota(
            jnp.int32, (bm, 1), 0)
        live = (row < n_orig) & (y != cfg.ignore_index)
        tile_max = jnp.max(jnp.max(jnp.where(live, z, _NEG_INF), axis=1,
                                   keepdims=True), axis=0, keepdims=True)
        # lane v of the row block's resident (1, num_v) stats vector
        lane = jax.lax.broadcasted_iota(jnp.int32, tmax_ref.shape, 1)
        tmax_ref[...] = jnp.where(lane == v, tile_max, tmax_ref[...])

    @pl.when(v == num_v - 1)
    def _epilogue():
        lse_ref[...] = m_sc[...] + jnp.log(a_sc[...])
        ztgt_ref[...] = zt_sc[...]
        zsum_ref[...] = zs_sc[...]


def fwd_stats(
    h: jax.Array, w: jax.Array, y: jax.Array, cfg: LossConfig,
    plan: Optional[BlockPlan] = None, interpret: Optional[bool] = None,
    *, col_offset=0, total_valid: Optional[int] = None,
    return_tile_stats: bool = False,
    w_scale: Optional[jax.Array] = None,
):
    """Per-row (lse, z_target, z_sum) via the forward Pallas kernel.

    h: (N, d), w: (V, d), y: (N,) int32.  N and V are padded internally to
    the block plan; pad rows/cols never influence real outputs.

    `w_scale` (V,) f32 marks `w` as row-quantized (int8/fp8, see
    `kernels/quant.quantize_weight`): W tiles stream at 1 byte/element
    and each logits tile is rescaled in-register before the softcap
    (DESIGN.md §10.2).  Forward/eval only — `bwd_grads` refuses
    quantized weights.

    With `return_tile_stats=True` a fourth output is returned: the
    (num_row_blocks, num_vocab_blocks) f32 per-tile max logit over live
    rows (DESIGN.md §9) — the gradient-filter statistic `bwd_grads`
    turns into its skip mask.  The (lse, z_target, z_sum) arithmetic is
    identical either way.

    Tensor-parallel shards pass `col_offset` (traced scalar: global id of
    w's first row) and `total_valid` (global valid vocab); `y` stays global.
    """
    n, d = h.shape
    v_orig = w.shape[0]
    valid = total_valid if total_valid is not None else (
        cfg.resolve_vocab(v_orig))
    quantized = w_scale is not None
    plan = plan or choose_blocks(n, v_orig, d, in_bytes=w.dtype.itemsize)
    bm, bv = plan.block_rows, plan.block_v
    interpret = interpret_default() if interpret is None else interpret

    n_pad = (-n) % bm
    v_pad = (-v_orig) % bv
    if n_pad:
        h = jnp.pad(h, ((0, n_pad), (0, 0)))
        y = jnp.pad(y, (0, n_pad), constant_values=0)
    if v_pad:
        w = jnp.pad(w, ((0, v_pad), (0, 0)))
    np_, vp = h.shape[0], w.shape[0]
    num_r, num_v = np_ // bm, vp // bv

    off = jnp.asarray(col_offset, jnp.int32).reshape(1, 1)
    y2 = y.astype(jnp.int32)[:, None]                       # (N, 1)
    out_shape = [jax.ShapeDtypeStruct((np_, 1), jnp.float32)] * 3
    out_specs = [pl.BlockSpec((bm, 1), lambda r, v: (r, 0))] * 3
    if return_tile_stats:
        out_shape.append(
            jax.ShapeDtypeStruct((num_r, 1, num_v), jnp.float32))
        out_specs.append(
            pl.BlockSpec((None, 1, num_v), lambda r, v: (r, 0, 0)))
    kern = functools.partial(_fwd_kernel, cfg=cfg, valid=valid,
                             v_orig=v_orig, bv=bv, num_v=num_v,
                             n_orig=n, emit_stats=return_tile_stats,
                             quantized=quantized)
    in_specs = [
        pl.BlockSpec((1, 1), lambda r, v: (0, 0)),          # col offset
        pl.BlockSpec((bm, 1), lambda r, v: (r, 0)),         # y
        pl.BlockSpec((bm, d), lambda r, v: (r, 0)),         # h
        pl.BlockSpec((bv, d), lambda r, v: (v, 0)),         # w
    ]
    inputs = [off, y2, h, w]
    if quantized:
        ws = jnp.pad(w_scale.astype(jnp.float32), (0, v_pad))[None, :]
        in_specs.append(pl.BlockSpec((1, bv), lambda r, v: (0, v)))
        inputs.append(ws)
    outs = pl.pallas_call(
        kern,
        grid=(num_r, num_v),
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM((bm, 1), jnp.float32) for _ in range(4)],
        compiler_params=compiler_params(
            tile_bytes(bm, bv, d, in_bytes=h.dtype.itemsize)),
        interpret=interpret,
        name="fused_ce_fwd",
    )(*inputs)
    lse, ztgt, zsum = (o[:n, 0] for o in outs[:3])
    if return_tile_stats:
        return lse, ztgt, zsum, outs[3][:, 0, :]
    return lse, ztgt, zsum


# ---------------------------------------------------------------------------
# Backward kernels (two-pass; logit recompute per tile)
# ---------------------------------------------------------------------------


def _grad_tile(h_tile, w_tile, y_tile, lse_tile, gamma_tile, pc_tile,
               v_start, col_offset, cfg: LossConfig, valid: int,
               v_orig: int):
    """g = Γ·(p·(1+2λ_z·lse) − (1−ε)·onehot − ε/valid) for one tile."""
    z = _mxu_dot(h_tile, w_tile, ((1,), (1,)))
    if cfg.logit_softcap is not None:
        cap = jnp.float32(cfg.logit_softcap)
        zc = cap * jnp.tanh(z / cap)
    else:
        zc = z
    bm, bv = zc.shape
    local_col = v_start + jax.lax.broadcasted_iota(jnp.int32, (bm, bv), 1)
    col = local_col + col_offset
    col_valid = (local_col < v_orig) & (col < valid)
    p = jnp.exp(jnp.where(col_valid, zc, _NEG_INF) - lse_tile)
    onehot = (col == y_tile).astype(jnp.float32)
    eps = jnp.float32(cfg.label_smoothing)
    g = pc_tile * p - gamma_tile * ((1.0 - eps) * onehot + eps / valid)
    if cfg.logit_softcap is not None:
        g = g * (1.0 - (zc / jnp.float32(cfg.logit_softcap)) ** 2)
    return jnp.where(col_valid, g, 0.0)


def _dh_kernel(*refs, cfg: LossConfig, valid: int, v_orig: int, bv: int,
               num_v: int, filtered: bool):
    """dH for one row tile, accumulated over the sequential vocab axis
    straight into the resident f32 output block.  `filtered` prepends the
    scalar-prefetched skip mask (flat (num_r * num_v,) int32 in SMEM):
    the tile recompute + MXU accumulate never run for masked tiles
    (DESIGN.md §9); init stays unconditional."""
    if filtered:
        skip_ref, *refs = refs
    off_ref, y_ref, lse_ref, gm_ref, pc_ref, h_ref, w_ref, dh_ref = refs
    r, v = pl.program_id(0), pl.program_id(1)

    @pl.when(v == 0)
    def _init():
        dh_ref[...] = jnp.zeros_like(dh_ref[...])

    def _accumulate():
        g = _grad_tile(h_ref[...], w_ref[...], y_ref[...], lse_ref[...],
                       gm_ref[...], pc_ref[...], v * bv, off_ref[0, 0],
                       cfg, valid, v_orig)
        # dH_tile += g @ W_tile      (bm,bv)x(bv,d) on the MXU
        dh_ref[...] += _grad_dot(g, w_ref[...], ((1,), (0,)))

    if filtered:
        pl.when(skip_ref[r * num_v + v] == 0)(_accumulate)
    else:
        _accumulate()


def _dw_kernel(*refs, cfg: LossConfig, valid: int, v_orig: int, bv: int,
               num_v: int, filtered: bool):
    """dW for one vocab tile, accumulated over the sequential row axis
    into the resident f32 output block; `filtered` as in `_dh_kernel`
    (the same (num_r, num_v) mask, read transposed: this grid is
    (v, r)-major)."""
    if filtered:
        skip_ref, *refs = refs
    off_ref, y_ref, lse_ref, gm_ref, pc_ref, h_ref, w_ref, dw_ref = refs
    v, r = pl.program_id(0), pl.program_id(1)

    @pl.when(r == 0)
    def _init():
        dw_ref[...] = jnp.zeros_like(dw_ref[...])

    def _accumulate():
        g = _grad_tile(h_ref[...], w_ref[...], y_ref[...], lse_ref[...],
                       gm_ref[...], pc_ref[...], v * bv, off_ref[0, 0],
                       cfg, valid, v_orig)
        # dW_tile += g^T @ H_tile    (bv,bm)x(bm,d) on the MXU
        dw_ref[...] += _grad_dot(g, h_ref[...], ((0,), (0,)))

    if filtered:
        pl.when(skip_ref[r * num_v + v] == 0)(_accumulate)
    else:
        _accumulate()


def bwd_grads(
    h: jax.Array, w: jax.Array, y: jax.Array,
    lse: jax.Array, gamma: jax.Array, p_coeff: jax.Array,
    cfg: LossConfig, plan: Optional[BlockPlan] = None,
    interpret: Optional[bool] = None,
    *, col_offset=0, total_valid: Optional[int] = None,
    tile_stats: Optional[jax.Array] = None,
    skip_mask: Optional[jax.Array] = None,
) -> Tuple[jax.Array, jax.Array]:
    """(dH, dW) via the two backward Pallas kernels (f32 outputs).

    Gradient filtering (DESIGN.md §9): pass `tile_stats` — the fourth
    output of `fwd_stats(..., return_tile_stats=True)` under the SAME
    plan — and, with `cfg.grad_filter_eps > 0`, vocab tiles whose
    softmax-mass bound falls below the threshold are skipped in both
    kernels.  `skip_mask` overrides the derived (num_r, num_v) boolean
    mask directly (tests force all-False to prove the filtered kernels
    are bit-identical to the exact ones).  With neither, this is the
    exact backward, bit-for-bit the code that predates the filter.
    """
    if w.dtype.itemsize == 1:
        raise NotImplementedError(
            "fused-CE backward does not support quantized lm_head weights "
            f"(w.dtype={w.dtype.name}); quantized heads are forward/eval "
            "only (DESIGN.md §10.2) — keep a bf16 master weight for "
            "training")
    n, d = h.shape
    v_orig = w.shape[0]
    valid = total_valid if total_valid is not None else (
        cfg.resolve_vocab(v_orig))
    plan = plan or choose_blocks(n, v_orig, d, in_bytes=h.dtype.itemsize)
    bm, bv = plan.block_rows, plan.block_v
    interpret = interpret_default() if interpret is None else interpret

    if skip_mask is None and tile_stats is not None and cfg.filter_grads:
        from repro.core.filtering import tile_skip_mask
        skip_mask = tile_skip_mask(tile_stats, lse, y, cfg, block_rows=bm,
                                   block_v=bv, col_offset=col_offset)

    n_pad = (-n) % bm
    v_pad = (-v_orig) % bv
    if n_pad:
        h = jnp.pad(h, ((0, n_pad), (0, 0)))
        y = jnp.pad(y, (0, n_pad), constant_values=0)
        lse = jnp.pad(lse, (0, n_pad))
        gamma = jnp.pad(gamma, (0, n_pad))       # pad rows: gamma == 0
        p_coeff = jnp.pad(p_coeff, (0, n_pad))
    if v_pad:
        w = jnp.pad(w, ((0, v_pad), (0, 0)))
    np_, vp = h.shape[0], w.shape[0]
    num_r, num_v = np_ // bm, vp // bv

    off = jnp.asarray(col_offset, jnp.int32).reshape(1, 1)
    y2 = y.astype(jnp.int32)[:, None]
    lse2, gm2, pc2 = lse[:, None], gamma[:, None], p_coeff[:, None]

    filtered = skip_mask is not None
    prefetch = ()
    if filtered:
        if skip_mask.shape != (num_r, num_v):
            raise ValueError(
                f"skip mask shape {skip_mask.shape} does not match the "
                f"backward grid {(num_r, num_v)} of plan {plan.shape}")
        # scalar-prefetched into SMEM: the kernels branch on it per tile
        prefetch = (skip_mask.astype(jnp.int32).reshape(-1),)
    kw = dict(cfg=cfg, valid=valid, v_orig=v_orig, bv=bv, num_v=num_v,
              filtered=filtered)
    args = prefetch + (off, y2, lse2, gm2, pc2, h, w)
    vmem = bwd_tile_bytes(bm, bv, d, in_bytes=h.dtype.itemsize)

    def call(kernel, name, grid, row_axis, out_rows, out_block_rows):
        # index maps see the grid indices, then the prefetched mask ref;
        # the output tile follows the outer (parallel) grid axis
        rows = lambda *i: (i[row_axis], 0)
        cols = lambda *i: (i[1 - row_axis], 0)
        in_specs = ([pl.BlockSpec((1, 1), lambda *i: (0, 0))]   # col offset
                    + [pl.BlockSpec((bm, 1), rows)] * 4  # y lse gamma p_coeff
                    + [pl.BlockSpec((bm, d), rows),             # h
                       pl.BlockSpec((bv, d), cols)])            # w
        return pl.pallas_call(
            functools.partial(kernel, **kw),
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=len(prefetch), grid=grid,
                in_specs=in_specs,
                out_specs=pl.BlockSpec((out_block_rows, d),
                                       lambda *i: (i[0], 0))),
            out_shape=jax.ShapeDtypeStruct((out_rows, d), jnp.float32),
            compiler_params=compiler_params(vmem),
            interpret=interpret,
            name=name,
        )(*args)

    dh = call(_dh_kernel, "fused_ce_dh", (num_r, num_v), 0, np_, bm)
    dw = call(_dw_kernel, "fused_ce_dw", (num_v, num_r), 1, vp, bv)
    return dh[:n], dw[:v_orig]
