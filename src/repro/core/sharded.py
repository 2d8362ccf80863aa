"""Distributed fused projection+CE — paper §3.2.2 (DP / TP / SP) on a mesh.

Two layouts are provided, both as a single `custom_vjp` whose forward and
backward are `shard_map` regions (so the collective schedule is explicit and
AD never materializes logits):

  layout='2d'  (beyond-paper default)
      rows (B*T) sharded over `rows_axes` (the data/pod axes), vocab sharded
      over `vocab_axis` (the model axis).  Every device streams its own
      (rows_local × vocab_local) panel with the local kernel, then the
      per-window merge of the paper (§3.2.1 epilogue) is executed ACROSS
      CHIPS:   lse  = logsumexp-combine over vocab shards (pmax + psum),
               z*   = psum (only the owner shard contributes),
               Σz   = psum.
      Forward cross-chip traffic: O(rows_local) scalars — 3 f32 per row.
      Backward: dH = psum over vocab shards of the partial G·W (f32,
      rows_local × d); dW stays local (exact vocab slice).

  layout='sp_gather'  (paper-faithful SP→TP conversion, Fig. 3c)
      rows additionally sharded over `vocab_axis` (sequence parallelism).
      hidden states are first all-gathered over the vocab axis — "gathering
      partial hidden states and converting the SP layout into a TP
      compatible pattern" — then the TP path runs; backward reduce-scatters
      dH back to the SP layout.  Traffic: O(rows_local·d) all-gather fwd +
      reduce-scatter bwd.  Kept for faithful comparison; '2d' strictly
      dominates it (see EXPERIMENTS §Perf).

Both layouts accept impl='streaming' (lax.scan) or impl='pallas' (TPU
kernels with global column ids via `col_offset`).
"""

from __future__ import annotations

import dataclasses
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.core.types import LossConfig
from repro.core.windows import BlockPlan
from repro.core.streaming import (
    streaming_stats, streaming_grads, _rows_from_stats)

Mesh = jax.sharding.Mesh


def _local_stats(h, w, y, cfg, impl, col_offset, total_valid, plan=None,
                 return_tile_stats=False):
    """Per-shard forward stats; with `return_tile_stats` a fourth output
    carries the grad-filter tile maxima (DESIGN.md §9), normalized to a
    2-D (row_blocks, vocab_blocks) layout for both impls — streaming has
    a single row block spanning all local rows."""
    if impl == "pallas":
        from repro.kernels.fused_ce.kernel import fwd_stats
        return fwd_stats(h, w, y, cfg, plan=plan, col_offset=col_offset,
                         total_valid=total_valid,
                         return_tile_stats=return_tile_stats)
    out = streaming_stats(h, w, y, cfg, col_offset=col_offset,
                          total_valid=total_valid,
                          return_tile_stats=return_tile_stats)
    if return_tile_stats:
        lse, zt, zs, tmax = out
        return lse, zt, zs, tmax[None, :]
    return out


def _local_grads(h, w, y, lse, gamma, p_coeff, cfg, impl, col_offset,
                 total_valid, plan=None, tile_stats=None):
    """Per-shard backward; `tile_stats` (when filtering) is this shard's
    LOCAL tile-max panel — the skip mask is derived against the globally
    combined `lse` with the shard's own `col_offset`, so a target owned
    by another shard never pins a tile here."""
    if impl == "pallas":
        from repro.kernels.fused_ce.kernel import bwd_grads
        return bwd_grads(h, w, y, lse, gamma, p_coeff, cfg, plan=plan,
                         col_offset=col_offset, total_valid=total_valid,
                         tile_stats=tile_stats)
    # streaming_grads folds p_coeff internally from (gamma, z_loss, lse)
    dh, dw = streaming_grads(h, w, y, lse, gamma, cfg,
                             col_offset=col_offset, total_valid=total_valid,
                             tile_stats=(None if tile_stats is None
                                         else tile_stats[0]))
    return dh.astype(jnp.float32), dw.astype(jnp.float32)


def _combine_lse(lse_local, vocab_axis):
    """logsumexp-combine of per-shard lse over the vocab axis.

    This is the paper's window-epilogue executed across chips: each shard's
    lse plays the role of one window's (m, a) folded into a single scalar.
    """
    m = jax.lax.pmax(lse_local, vocab_axis)
    safe_m = jnp.where(jnp.isneginf(m), 0.0, m)
    a = jax.lax.psum(jnp.exp(lse_local - safe_m), vocab_axis)
    return safe_m + jnp.log(a)


def make_sharded_loss(
    mesh: Mesh,
    cfg: Optional[LossConfig] = None,
    *,
    rows_axes: Sequence[str] = ("data",),
    vocab_axis: str = "model",
    layout: str = "2d",
    impl: str = "streaming",
    plan: Optional[BlockPlan] = None,
):
    """Build a differentiable sharded fused-CE:  f(h, w, y) -> scalar loss.

    Expected global shapes / shardings (callers flatten (B,T,d) first):
      h: (N, d)   rows over rows_axes       ('2d')
                  rows over rows_axes+vocab ('sp_gather')
      w: (V, d)   vocab over vocab_axis; V must divide evenly — pad W and
                  set cfg.valid_vocab (mask handled in-kernel).
      y: (N,)     sharded like h's rows.

    reduction must be 'mean' or 'sum' (a global scalar).

    `plan` is the per-shard block plan (DESIGN.md §3.2): every device
    streams its LOCAL (rows_local × vocab_local) panel, so tune/key on the
    local shapes — rows_local = N / prod(rows_axes) and
    vocab_local = V / mesh.shape[vocab_axis] — not the global ones.
    For impl='streaming' only `plan.block_v` applies (window size);
    for impl='pallas' it sets the kernel tile shape.
    """
    cfg = cfg or LossConfig()
    if plan is not None and impl == "streaming":
        cfg = dataclasses.replace(cfg, block_v=plan.block_v)
    if cfg.reduction not in ("mean", "sum"):
        raise ValueError("sharded loss requires a scalar reduction")
    if layout not in ("2d", "sp_gather"):
        raise ValueError(f"unknown layout {layout!r}")
    rows_axes = tuple(rows_axes)
    n_vocab_shards = mesh.shape[vocab_axis]

    row_axes_all = rows_axes + (vocab_axis,) if layout == "sp_gather" \
        else rows_axes
    h_spec = P(row_axes_all, None)
    y_spec = P(row_axes_all)
    w_spec = P(vocab_axis, None)

    def _offset(v_local):
        idx = jax.lax.axis_index(vocab_axis)
        return (idx * v_local).astype(jnp.int32)

    # gradient filtering (DESIGN.md §9): each shard's LOCAL tile-max panel
    # rides the residuals — rows blocked over the shard's (gathered) rows,
    # vocab blocked over its local vocab slice, so the residual spec is
    # rows over rows_axes x vocab over vocab_axis for both layouts.
    filtering = cfg.filter_grads
    tmax_spec = P(rows_axes, vocab_axis)

    # ---------------- forward ----------------
    def _fwd_shard(h_l, w_l, y_l):
        if layout == "sp_gather":
            # paper Fig 3(c): gather SP rows into the TP layout
            h_l = jax.lax.all_gather(h_l, vocab_axis, axis=0, tiled=True)
            y_l = jax.lax.all_gather(y_l, vocab_axis, axis=0, tiled=True)
        v_local = w_l.shape[0]
        total_valid = cfg.resolve_vocab(v_local * n_vocab_shards)
        stats = _local_stats(
            h_l, w_l, y_l, cfg, impl, _offset(v_local), total_valid,
            plan=plan, return_tile_stats=filtering)
        lse_p, zt_p, zs_p = stats[:3]
        lse = _combine_lse(lse_p, vocab_axis)
        z_tgt = jax.lax.psum(zt_p, vocab_axis)
        z_sum = jax.lax.psum(zs_p, vocab_axis)
        rows = _rows_from_stats(lse, z_tgt, z_sum, y_l, total_valid, cfg)
        keep = (y_l != cfg.ignore_index).astype(jnp.float32)
        # row reduction: sum over local rows then over all row shards.  In
        # sp_gather each TP rank holds the same gathered rows -> divide.
        local_sum = jnp.sum(rows)
        local_cnt = jnp.sum(keep)
        total = jax.lax.psum(local_sum, rows_axes)
        count = jax.lax.psum(local_cnt, rows_axes)
        if cfg.reduction == "mean":
            loss = total / jnp.maximum(count, 1.0)
        else:
            loss = total
        if filtering:
            return loss, lse, count, stats[3]
        return loss, lse, count

    fwd_out_specs = (P(), P(rows_axes), P())
    if filtering:
        fwd_out_specs = fwd_out_specs + (tmax_spec,)
    fwd_sharded = jax.shard_map(
        _fwd_shard, mesh=mesh,
        in_specs=(h_spec, w_spec, y_spec),
        out_specs=fwd_out_specs,
        check_vma=False,
    )

    # residual lse is produced in the TP row layout (rows over rows_axes,
    # replicated over vocab_axis) for both layouts.

    # ---------------- backward ----------------
    def _bwd_shard(h_l, w_l, y_l, lse_l, gamma_l, tmax_l=None):
        if layout == "sp_gather":
            h_l = jax.lax.all_gather(h_l, vocab_axis, axis=0, tiled=True)
            y_l = jax.lax.all_gather(y_l, vocab_axis, axis=0, tiled=True)
        v_local = w_l.shape[0]
        total_valid = cfg.resolve_vocab(v_local * n_vocab_shards)
        p_coeff = gamma_l * (1.0 + 2.0 * jnp.float32(cfg.z_loss) * lse_l)
        dh_p, dw_l = _local_grads(
            h_l, w_l, y_l, lse_l, gamma_l, p_coeff, cfg, impl,
            _offset(v_local), total_valid, plan=plan, tile_stats=tmax_l)
        if layout == "sp_gather":
            # reduce-scatter dH back to the SP layout (paper Fig 3c reverse)
            dh = jax.lax.psum_scatter(dh_p, vocab_axis, scatter_dimension=0,
                                      tiled=True)
        else:
            dh = jax.lax.psum(dh_p, vocab_axis)
        # every row shard holds a partial dW for its rows only -> DP grad
        # all-reduce (this is the standard DP gradient sync of Fig 3a).
        dw = jax.lax.psum(dw_l, rows_axes)
        return dh.astype(h_l.dtype), dw.astype(w_l.dtype)

    bwd_in_specs = (h_spec, w_spec, y_spec, P(rows_axes), P(rows_axes))
    if filtering:
        bwd_in_specs = bwd_in_specs + (tmax_spec,)
    bwd_sharded = jax.shard_map(
        _bwd_shard, mesh=mesh,
        in_specs=bwd_in_specs,
        out_specs=(h_spec, w_spec),
        check_vma=False,
    )

    # ---------------- custom_vjp assembly ----------------
    @jax.custom_vjp
    def loss_fn(h, w, y):
        return fwd_sharded(h, w, y)[0]

    def loss_fwd(h, w, y):
        out = fwd_sharded(h, w, y)
        loss, lse, count = out[:3]
        tmax = out[3] if filtering else None
        return loss, (h, w, y, lse, count, tmax)

    def loss_bwd(res, gbar):
        h, w, y, lse, count, tmax = res
        gbar = jnp.asarray(gbar, jnp.float32)

        def _gamma(y_l, count):
            keep = (y_l != cfg.ignore_index).astype(jnp.float32)
            if cfg.reduction == "mean":
                return gbar * keep / jnp.maximum(count, 1.0)
            return gbar * keep

        gamma = jax.shard_map(
            _gamma, mesh=mesh,
            in_specs=(P(rows_axes), P()), out_specs=P(rows_axes),
            check_vma=False,
        )(y if layout == "2d" else _regather_rows(y), count)
        args = (h, w, y, lse, gamma) + ((tmax,) if filtering else ())
        dh, dw = bwd_sharded(*args)
        dy = np.zeros(y.shape, dtype=jax.dtypes.float0)
        return dh, dw, dy

    def _regather_rows(y):
        # sp_gather: y is SP-sharded globally; the TP-layout gamma/lse rows
        # are the same global array — specs differ only in sharding.
        return y

    loss_fn.defvjp(loss_fwd, loss_bwd)
    return loss_fn
