"""Pure-JAX streaming fused projection + cross-entropy (paper Alg. 1 + Alg. 2).

This is the faithful reproduction of the paper's algorithm expressed with
`jax.lax` control flow: the vocabulary axis is streamed in chunks ("windows",
§3.2.1) and the numerically-stable online-softmax state

    m  — running maximum logit            (paper line 4 / 9-13)
    a  — rescaled exponential accumulator (paper line 5 / 10,13)
    z* — the target logit                 (paper line 15-16)

is carried across chunks.  The full (N, V) logits tensor is NEVER formed:
peak intermediate memory is O(N * block_v) for the in-flight tile plus O(N)
for the state — matching the paper's O(B*T) claim up to the tile.

The backward pass (`custom_vjp`) re-streams the vocabulary, recomputes each
logit tile, forms  g = gamma * (softmax - onehot)  on the fly and contracts it
into dH and dW (paper Alg. 2), again without materializing logits.

This implementation is also the semantic oracle for the Pallas TPU kernel in
`repro.kernels.fused_ce` and runs on any backend.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.types import LossConfig
from repro.core.canonical import reduce_loss
from repro.core.windows import BlockPlan

_NEG_INF = float("-inf")


def _num_chunks(v_padded: int, block_v: int) -> int:
    return -(-v_padded // block_v)


def _pad_vocab(w: jax.Array, block_v: int) -> jax.Array:
    """Pad W rows so the chunk count divides evenly (pads are masked)."""
    v = w.shape[0]
    rem = (-v) % block_v
    if rem:
        w = jnp.pad(w, ((0, rem), (0, 0)))
    return w


def _chunk_logits(h32, w_chunk, local_start, col_offset, v_orig, valid,
                  cfg: LossConfig):
    """One logits tile z = h @ w_chunk^T with softcap + pad masking.

    A column is valid iff it is structurally real (local index < v_orig,
    i.e. not local block padding) AND its *global* id (local + col_offset)
    is < `valid`.  In the unsharded case col_offset == 0 and v_orig == V.
    Returns (z, global_col, col_valid); invalid columns hold -inf in z.
    """
    bv = w_chunk.shape[0]
    z = jnp.dot(h32, w_chunk.T.astype(jnp.float32),
                preferred_element_type=jnp.float32)
    if cfg.logit_softcap is not None:
        cap = jnp.float32(cfg.logit_softcap)
        z = cap * jnp.tanh(z / cap)
    local_col = local_start + jnp.arange(bv, dtype=jnp.int32)
    col = col_offset + local_col
    col_valid = (local_col < v_orig) & (col < valid)
    z = jnp.where(col_valid[None, :], z, _NEG_INF)
    return z, col, col_valid


# ---------------------------------------------------------------------------
# Forward (Alg. 1, chunked): returns per-row statistics.
# ---------------------------------------------------------------------------


def streaming_stats(
    h: jax.Array, w: jax.Array, y: jax.Array, cfg: LossConfig,
    *, col_offset=0, total_valid: Optional[int] = None,
    return_tile_stats: bool = False,
):
    """Stream the vocab; return per-row (lse, z_target, z_sum).

    z_sum (sum of valid logits) is needed only for label smoothing; it is
    computed unconditionally because it is one extra VPU add per tile.

    With `return_tile_stats=True` a fourth array is returned: the
    per-chunk max logit over live rows and valid columns — shape
    (n_chunks,), the gradient-filtering statistic of DESIGN.md §9
    (ignore-masked rows are excluded so the backward's skip mask is
    invariant to their hidden states).  The (lse, z_target, z_sum)
    arithmetic is untouched either way.

    For tensor-parallel shards: `w` is the local vocab slice, `col_offset`
    (traced OK) is the global id of its first row, and `total_valid` the
    global valid-vocab size; `y` keeps global token ids.  Rows whose target
    lies outside this shard get z_target == 0 (merged later via psum).
    """
    n, d = h.shape
    v_orig = w.shape[0]
    valid = total_valid if total_valid is not None else (
        cfg.resolve_vocab(v_orig))
    w = _pad_vocab(w, cfg.block_v)
    n_chunks = w.shape[0] // cfg.block_v
    w_chunks = w.reshape(n_chunks, cfg.block_v, d)

    h32 = h.astype(jnp.float32)
    y = y.astype(jnp.int32)
    col_offset = jnp.asarray(col_offset, jnp.int32)
    live_row = (y != cfg.ignore_index)                     # (n,)

    def body(carry, inputs):
        m, a, z_sum, z_tgt = carry
        w_chunk, idx = inputs
        start = idx * cfg.block_v
        z, col, col_valid = _chunk_logits(
            h32, w_chunk, start, col_offset, v_orig, valid, cfg)
        # --- online max/accumulator update (paper lines 8-14) ---
        chunk_max = jnp.max(z, axis=-1)                    # (n,)
        m_new = jnp.maximum(m, chunk_max)
        # guard exp(-inf - -inf): only possible if every column so far is
        # padding, which cannot happen for valid >= 1, but keep it total.
        safe_m = jnp.where(jnp.isneginf(m_new), 0.0, m_new)
        a = a * jnp.exp(m - safe_m) + jnp.sum(jnp.exp(z - safe_m[:, None]),
                                              axis=-1)
        # --- auxiliary running sums ---
        z_sum = z_sum + jnp.sum(jnp.where(col_valid[None, :], z, 0.0), axis=-1)
        # col_valid guard: a shard's local PAD columns alias global ids of
        # the next shard and must never match a target
        is_tgt = (col[None, :] == y[:, None]) & col_valid[None, :]
        z_tgt = z_tgt + jnp.sum(jnp.where(is_tgt, z, 0.0), axis=-1)
        ys = None
        if return_tile_stats:
            ys = jnp.max(jnp.where(live_row, chunk_max, _NEG_INF))
        return (m_new, a, z_sum, z_tgt), ys

    init = (
        jnp.full((n,), _NEG_INF, dtype=jnp.float32),
        jnp.zeros((n,), dtype=jnp.float32),
        jnp.zeros((n,), dtype=jnp.float32),
        jnp.zeros((n,), dtype=jnp.float32),
    )
    (m, a, z_sum, z_tgt), tmax = jax.lax.scan(
        body, init, (w_chunks, jnp.arange(n_chunks, dtype=jnp.int32)))
    lse = m + jnp.log(a)
    if return_tile_stats:
        return lse, z_tgt, z_sum, tmax
    return lse, z_tgt, z_sum


def _rows_from_stats(lse, z_tgt, z_sum, y, valid, cfg: LossConfig):
    loss = lse - z_tgt
    if cfg.label_smoothing > 0.0:
        eps = jnp.float32(cfg.label_smoothing)
        loss = (1.0 - eps) * loss + eps * (lse - z_sum / valid)
    if cfg.z_loss > 0.0:
        loss = loss + jnp.float32(cfg.z_loss) * lse * lse
    return jnp.where(y != cfg.ignore_index, loss, 0.0)


# ---------------------------------------------------------------------------
# Backward (Alg. 2, chunked recompute).
# ---------------------------------------------------------------------------


def _row_scale(gbar: jax.Array, y: jax.Array, cfg: LossConfig) -> jax.Array:
    """Per-row upstream scale gamma (paper's Γ)."""
    keep = (y != cfg.ignore_index).astype(jnp.float32)
    if cfg.reduction == "mean":
        denom = jnp.maximum(jnp.sum(keep), 1.0)
        return gbar * keep / denom
    if cfg.reduction == "sum":
        return gbar * keep
    return gbar * keep  # 'none': gbar is already per-row


def streaming_grads(
    h: jax.Array, w: jax.Array, y: jax.Array,
    lse: jax.Array, gamma: jax.Array, cfg: LossConfig,
    *, col_offset=0, total_valid: Optional[int] = None,
    tile_stats: Optional[jax.Array] = None,
) -> Tuple[jax.Array, jax.Array]:
    """dH, dW via chunked logit recompute (paper Alg. 2 / Appendix A.1).

    g_{n,v} = gamma_n * [ p_v * (1 + 2*zl*lse_n)
                          - (1-eps)*onehot - eps/valid ]        (valid cols)
    dH      = sum_chunks g_chunk @ W_chunk
    dW_chunk = g_chunk^T @ H

    Gradient filtering (DESIGN.md §9): when `cfg.grad_filter_eps > 0` and
    `tile_stats` carries the forward's per-chunk max logits, chunks whose
    softmax-mass bound falls below the threshold (and which contain no
    target id) are skipped via `lax.cond` — the tile GEMMs never run and
    their dH/dW contribution is exactly zero.  With `tile_stats=None` or
    eps == 0 the loop below is the exact backward, bit-identical to
    before the knob existed.

    Sharded use: pass the shard's `col_offset` / global `total_valid` and
    the *globally combined* lse — dH is then this shard's partial (psum it
    over the vocab axis); dW is the shard's exact local slice.
    """
    n, d = h.shape
    v_orig = w.shape[0]
    valid = total_valid if total_valid is not None else (
        cfg.resolve_vocab(v_orig))
    w_pad = _pad_vocab(w, cfg.block_v)
    n_chunks = w_pad.shape[0] // cfg.block_v
    w_chunks = w_pad.reshape(n_chunks, cfg.block_v, d)

    h32 = h.astype(jnp.float32)
    y = y.astype(jnp.int32)
    col_offset = jnp.asarray(col_offset, jnp.int32)
    eps = jnp.float32(cfg.label_smoothing)
    # row-wise coefficient applied to p_v (softmax part).
    p_coeff = gamma * (1.0 + 2.0 * jnp.float32(cfg.z_loss) * lse)

    filtering = cfg.filter_grads and tile_stats is not None
    if filtering:
        from repro.core.filtering import tile_skip_mask
        # one row block spanning the whole batch: the scan streams all
        # rows at once, so the skip decision is per vocab chunk only
        skip = tile_skip_mask(
            tile_stats[None, :], lse, y, cfg, block_rows=n,
            block_v=cfg.block_v, col_offset=col_offset)[0]   # (n_chunks,)

    def compute(dh, w_chunk, idx):
        start = idx * cfg.block_v
        z, col, col_valid = _chunk_logits(
            h32, w_chunk, start, col_offset, v_orig, valid, cfg)
        p = jnp.exp(z - lse[:, None])                       # (n, bv)
        is_tgt = (col[None, :] == y[:, None]).astype(jnp.float32)
        g = (p_coeff[:, None] * p
             - gamma[:, None] * ((1.0 - eps) * is_tgt + eps / valid))
        if cfg.logit_softcap is not None:
            cap = jnp.float32(cfg.logit_softcap)
            g = g * (1.0 - (z / cap) ** 2)                  # d z'/d z_raw
        g = jnp.where(col_valid[None, :], g, 0.0)
        dh = dh + jnp.dot(g, w_chunk.astype(jnp.float32),
                          preferred_element_type=jnp.float32)
        # each dW chunk is a complete f32-accumulated sum over rows; store
        # it in the weight dtype (the paper keeps f32 only in registers) —
        # this keeps the stacked (V, d) gradient buffer at weight precision
        dw_chunk = jnp.dot(g.T, h32, preferred_element_type=jnp.float32
                           ).astype(w_chunk.dtype)
        return dh, dw_chunk

    def body(dh, inputs):
        w_chunk, idx = inputs
        return compute(dh, w_chunk, idx)

    def body_filtered(dh, inputs):
        w_chunk, idx, skip_chunk = inputs
        return jax.lax.cond(
            skip_chunk,
            lambda dh, w_chunk, idx: (
                dh, jnp.zeros((cfg.block_v, d), w_chunk.dtype)),
            compute, dh, w_chunk, idx)

    idxs = jnp.arange(n_chunks, dtype=jnp.int32)
    if filtering:
        dh, dw_chunks = jax.lax.scan(
            body_filtered, jnp.zeros((n, d), jnp.float32),
            (w_chunks, idxs, skip))
    else:
        dh, dw_chunks = jax.lax.scan(
            body, jnp.zeros((n, d), jnp.float32), (w_chunks, idxs))
    dw = dw_chunks.reshape(-1, d)[:v_orig]
    return dh.astype(h.dtype), dw.astype(w.dtype)


# ---------------------------------------------------------------------------
# custom_vjp assembly
# ---------------------------------------------------------------------------


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _streaming_loss(h, w, y, cfg: LossConfig):
    lse, z_tgt, z_sum = streaming_stats(h, w, y, cfg)
    valid = cfg.resolve_vocab(w.shape[0])
    rows = _rows_from_stats(lse, z_tgt, z_sum, y, valid, cfg)
    return reduce_loss(rows, y, cfg)


def _fwd(h, w, y, cfg: LossConfig):
    tmax = None
    if cfg.filter_grads:
        lse, z_tgt, z_sum, tmax = streaming_stats(h, w, y, cfg,
                                                  return_tile_stats=True)
    else:
        lse, z_tgt, z_sum = streaming_stats(h, w, y, cfg)
    valid = cfg.resolve_vocab(w.shape[0])
    rows = _rows_from_stats(lse, z_tgt, z_sum, y, valid, cfg)
    return reduce_loss(rows, y, cfg), (h, w, y, lse, tmax)


def _bwd(cfg: LossConfig, res, gbar):
    h, w, y, lse, tmax = res
    gamma = _row_scale(jnp.asarray(gbar, jnp.float32), y, cfg)
    dh, dw = streaming_grads(h, w, y, lse, gamma, cfg, tile_stats=tmax)
    dy = np.zeros(y.shape, dtype=jax.dtypes.float0)
    return dh, dw, dy


_streaming_loss.defvjp(_fwd, _bwd)


def streaming_loss(
    h: jax.Array,
    w: jax.Array,
    y: jax.Array,
    cfg: Optional[LossConfig] = None,
    plan: Optional[BlockPlan] = None,
) -> jax.Array:
    """Fused projection+CE, streaming over vocab chunks.  See module doc.

    Args:
      h: (N, d) hidden states.
      w: (V_padded, d) lm_head weights.
      y: (N,) int targets.
      cfg: loss configuration (`block_v` is the paper's window size).
      plan: optional `BlockPlan` (DESIGN.md §3.2); the scan streams
        whole rows, so only `plan.block_v` applies — it overrides
        `cfg.block_v` as the window size.
    """
    cfg = cfg or LossConfig()
    if plan is not None:
        cfg = dataclasses.replace(cfg, block_v=plan.block_v)
    return _streaming_loss(h, w, y, cfg)
