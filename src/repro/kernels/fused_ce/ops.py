"""Jitted, differentiable wrapper around the fused-CE Pallas kernels.

`pallas_loss(h, w, y, cfg)` is a drop-in replacement for
`repro.core.streaming.streaming_loss` (identical semantics, identical
custom_vjp structure), with the vocab streaming executed by the TPU kernels
in `kernel.py` instead of a `lax.scan`.

Block-plan selection (DESIGN.md §3): callers may pass an explicit
`BlockPlan`; when they don't, the plan is `choose_blocks` of the problem's
shape and the hidden states' dtype — a pure function, resolved at trace
time, that both kernels share.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.types import LossConfig
from repro.core.canonical import reduce_loss
from repro.core.streaming import _rows_from_stats, _row_scale
from repro.core.windows import BlockPlan, choose_blocks
from repro.kernels.fused_ce import kernel as K


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _pallas_loss(h, w, y, cfg: LossConfig, plan: Optional[BlockPlan]):
    lse, z_tgt, z_sum = K.fwd_stats(h, w, y, cfg, plan=plan)
    valid = cfg.resolve_vocab(w.shape[0])
    rows = _rows_from_stats(lse, z_tgt, z_sum, y, valid, cfg)
    return reduce_loss(rows, y, cfg)


def _fwd(h, w, y, cfg: LossConfig, plan: Optional[BlockPlan]):
    tmax = None
    if cfg.filter_grads:
        # the tile statistic rides the residuals (DESIGN.md §9): a few
        # bytes per (row-block, vocab-block), computed inside the same
        # online-softmax scan the forward runs anyway
        lse, z_tgt, z_sum, tmax = K.fwd_stats(h, w, y, cfg, plan=plan,
                                              return_tile_stats=True)
    else:
        lse, z_tgt, z_sum = K.fwd_stats(h, w, y, cfg, plan=plan)
    valid = cfg.resolve_vocab(w.shape[0])
    rows = _rows_from_stats(lse, z_tgt, z_sum, y, valid, cfg)
    return reduce_loss(rows, y, cfg), (h, w, y, lse, tmax)


def _bwd(cfg: LossConfig, plan: Optional[BlockPlan], res, gbar):
    h, w, y, lse, tmax = res
    gamma = _row_scale(jnp.asarray(gbar, jnp.float32), y, cfg)
    p_coeff = gamma * (1.0 + 2.0 * jnp.float32(cfg.z_loss) * lse)
    dh, dw = K.bwd_grads(h, w, y, lse, gamma, p_coeff, cfg, plan=plan,
                         tile_stats=tmax)
    dy = np.zeros(y.shape, dtype=jax.dtypes.float0)
    return dh.astype(h.dtype), dw.astype(w.dtype), dy


_pallas_loss.defvjp(_fwd, _bwd)


def pallas_loss(
    h: jax.Array,
    w: jax.Array,
    y: jax.Array,
    cfg: Optional[LossConfig] = None,
    plan: Optional[BlockPlan] = None,
    *,
    w_scale: Optional[jax.Array] = None,
) -> jax.Array:
    """Fused projection+CE via the Pallas TPU kernels.

    On non-TPU backends the kernels run in interpret mode (Python reference
    execution of the kernel body) — bit-for-bit the same algorithm.

    `plan` fixes the kernel tiling; `None` takes `choose_blocks` of the
    shape at the hidden states' itemsize, passed to the forward and the
    backward alike (the forward's own fallback sizes by `w`, which is
    1-byte for a quantized head).

    `w_scale` (V,) f32 marks `w` as row-quantized
    (`kernels/quant.quantize_weight`): the forward streams 1-byte W
    tiles with in-register rescale.  This path is forward/eval only (no
    custom VJP) — differentiating through it fails, by design: training
    keeps a full-precision master weight (DESIGN.md §10.2).
    """
    cfg = cfg or LossConfig()
    if plan is None:
        plan = choose_blocks(h.shape[0], w.shape[0], h.shape[-1],
                             in_bytes=h.dtype.itemsize)
    if w_scale is not None:
        lse, z_tgt, z_sum = K.fwd_stats(h, w, y, cfg, plan=plan,
                                        w_scale=w_scale)
        valid = cfg.resolve_vocab(w.shape[0])
        rows = _rows_from_stats(lse, z_tgt, z_sum, y, valid, cfg)
        return reduce_loss(rows, y, cfg)
    return _pallas_loss(h, w, y, cfg, plan)
