"""Pallas TPU kernels for the training step's causal GQA attention core.

The training call of `models/attention.py` (no cache, causal, no window
or softcap, unsharded) runs its online-softmax attention here: one
forward kernel and a two-kernel backward (dK/dV, then dQ; no TPU
atomics, the same split as `kernels/fused_ce`).  Each grid step holds
one (bt x bt) score tile per query head in VMEM; nothing of size T x T
reaches HBM, and the residuals are the jnp path's: q, k, v, out and the
row log-sum-exp.

Layouts.  The kernels take head-major (B, heads, T, hd) arrays, the
(B, T, heads, hd) tensors transposed.  XLA lays the projections out that
way from the start, so the transposes are free; a (B, T, heads*hd) view
would not be, since the TPU tiles the two minor dimensions (8, 128) and
such a reshape regroups the tiles, a copy of q, k and v per call.  The
g query heads of KV head n are adjacent, so one (g, bt, hd) block holds
the whole group and one (bt, hd) block its K or V: each K/V tile is
fetched once for the g heads, with no repeat of K/V in HBM.  lse and
D = rowsum(dO * O) are (B, nkv, g, T), the jnp path's lse layout, one
(g, bt) block per step.

Grids.  Queries and keys share one block size, so every grid is
(B*nkv*nb, nb): rows (parallel) are (batch, KV head, block), and the
innermost axis (sequential) walks the other sequence's blocks:

  forward : rows are query blocks, KV blocks innermost, out resident
  dQ      : rows are query blocks, KV blocks innermost, dQ resident
  dK/dV   : rows are KV blocks, query blocks innermost, dK/dV resident,
            summed over the g heads of the group

Causal work only: a step above the diagonal computes nothing, and its
index map is clamped to the diagonal block, so it asks for the block
its neighbouring step holds and the pipeline issues no DMA for it.
Only the diagonal tile builds a mask.

Arithmetic.  The jnp path's products run at XLA's default precision on
the TPU: one bf16 pass, operands rounded to nearest, f32 accumulation.
Every product here does the same (`_dot`: operands cast to bf16, f32
result).  q, k, v and dO are products' operands only, so the wrappers
round them to bf16 before the call, where XLA fuses the cast into their
producers: the kernels read half the bytes and no f32 copy of them is
kept for the kernels.  The softmax statistics, exp, the rescaling, D,
the output and the gradients stay f32.  The dK/dV kernel works on
transposed tiles (k rows, q columns), so lse and D enter it as lane rows
and it needs no transpose at all.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.pallas_utils import compiler_params, interpret_default

_LANE = 128
_SUBLANE = 8
_NT = ((1,), (1,))          # a @ b.T
_NN = ((1,), (0,))          # a @ b
# block sizes tried, largest first (on a v5e at T=4096, 1024 ran the
# forward 3% and the backward 3% faster than 512 rows of q with 1024 of
# k/v, and 2048-row blocks were slower); the VMEM models stay under
# the budget
_BLOCKS = (1024, 512, 256, 128)
_VMEM_BUDGET = 32 * 1024 * 1024


def choose_block(t: int, head_dim: int, group: int) -> Optional[int]:
    """Rows of q and of k/v per grid step for a (T, head_dim, group)
    problem, or None where the kernels cannot take it (head_dim off the
    128 lanes, T off 128 rows): the largest of `_BLOCKS` that divides T,
    halved while a kernel's VMEM model exceeds the budget."""
    if head_dim % _LANE or t % _LANE:
        return None
    bt = next(b for b in _BLOCKS if t % b == 0)
    while bt > _LANE and max(f(bt, head_dim, group) for f in _BYTES) \
            > _VMEM_BUDGET:
        bt //= 2
    return bt


def _fwd_bytes(bt, hd, g):
    """Forward VMEM bytes of one grid step: double-buffered bf16 q, k, v
    and f32 out and lse blocks (lse's g rows pad to 8 sublanes), the
    (bt, 1) m and l columns per head (lane-padded), and the step's f32
    score and probability tiles with the bf16 copy."""
    blocks = bt * g * hd * (2 + 4) + 2 * bt * hd * 2 + _SUBLANE * bt * 4
    return 2 * blocks + 2 * g * bt * _LANE * 4 + bt * bt * 10


def _dq_bytes(bt, hd, g):
    """dQ VMEM bytes of one grid step: double-buffered bf16 q, dO, k, v
    and f32 dQ, lse and D blocks, the lse and D columns, and the step's
    f32 s, p, dp, dS tiles with dS in bf16."""
    blocks = bt * g * hd * (2 * 2 + 4) + 2 * bt * hd * 2 \
        + 2 * _SUBLANE * bt * 4
    return 2 * blocks + 2 * g * bt * _LANE * 4 + bt * bt * 18


def _dkv_bytes(bt, hd, g):
    """dK/dV VMEM bytes of one grid step: double-buffered bf16 q, dO, k,
    v and f32 lse, D, dK and dV blocks, and the step's f32 sT, pT, dpT,
    dST tiles with pT and dST in bf16."""
    blocks = bt * g * hd * 2 * 2 + 2 * bt * hd * (2 + 4) \
        + 2 * _SUBLANE * bt * 4
    return 2 * blocks + bt * bt * 20


_BYTES = (_fwd_bytes, _dq_bytes, _dkv_bytes)


def _dot(a, b, dims):
    """One bf16 MXU pass with f32 accumulation: both operands rounded to
    bf16, as XLA's default precision runs an f32 dot on the TPU.  (An f32
    operand handed to Mosaic as is would be truncated, not rounded.)"""
    return jax.lax.dot_general(
        a.astype(jnp.bfloat16), b.astype(jnp.bfloat16), (dims, ((), ())),
        preferred_element_type=jnp.float32)


def _causal(s, rows_are_q):
    """Mask a diagonal tile: a query sees keys at or before it."""
    row = jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
    col = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    return jnp.where(col <= row if rows_are_q else row <= col, s, -jnp.inf)


def _column(row):
    """(1, n) lane row -> (n, 1) column, through one 2-D transpose."""
    return jnp.broadcast_to(row, (_LANE, row.shape[1])).T[:, :1]


def _row(col):
    """(n, 1) column -> (1, n) lane row."""
    return jnp.broadcast_to(col, (col.shape[0], _LANE)).T[:1, :]


def _heads_major(x, dtype=None):
    """(B, T, heads, hd) <-> (B, heads, T, hd), cast to `dtype` if
    given."""
    x = jnp.swapaxes(x, 1, 2)
    return x if dtype is None else x.astype(dtype)


def _grid_maps(nkv, nb, clamp):
    """Index maps of a (B*nkv*nb, nb) grid whose row r is (batch, KV
    head, own block x) and whose step c is a block of the other
    sequence, clamped by `clamp(c, x)` into the blocks row x sees.
    Returns the maps of the row's own (.., T, hd) block, of the other
    sequence's, and of the (.., g, T) statistics of each."""
    def split(r):
        return r // (nkv * nb), (r // nb) % nkv, r % nb

    def own(r, c):
        bi, n, x = split(r)
        return bi, n, x, 0

    def other(r, c):
        bi, n, x = split(r)
        return bi, n, clamp(c, x), 0

    def own_stat(r, c):
        bi, n, x = split(r)
        return bi, n, 0, x

    def other_stat(r, c):
        bi, n, x = split(r)
        return bi, n, 0, clamp(c, x)

    return own, other, own_stat, other_stat


# ---------------------------------------------------------------------------
# Forward
# ---------------------------------------------------------------------------


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, m_sc, l_sc, *,
                g, nb, scale):
    i = pl.program_id(0) % nb
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)
        m_sc[...] = jnp.full_like(m_sc, -jnp.inf)
        l_sc[...] = jnp.zeros_like(l_sc)

    def step(masked):
        k, v = k_ref[...], v_ref[...]
        for h in range(g):
            s = _dot(q_ref[h], k, _NT) * scale              # (bt, bt)
            if masked:
                s = _causal(s, rows_are_q=True)
            # column 0 is seen by every row in block 0: m stays finite
            m_prev = m_sc[h]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
            alpha = jnp.exp(m_prev - m_new)
            p = jnp.exp(s - m_new)
            l_sc[h] = alpha * l_sc[h] + jnp.sum(p, axis=1, keepdims=True)
            m_sc[h] = m_new
            o_ref[h] = alpha * o_ref[h] + _dot(p, v, _NN)

    pl.when(j < i)(lambda: step(False))
    pl.when(j == i)(lambda: step(True))

    @pl.when(j == nb - 1)
    def _epilogue():
        for h in range(g):
            o_ref[h] = o_ref[h] / l_sc[h]
            lse_ref[h:h + 1, :] = _row(m_sc[h] + jnp.log(l_sc[h]))


def flash_fwd(q, k, v, block: int, interpret: Optional[bool] = None
              ) -> Tuple[jax.Array, jax.Array]:
    """Causal attention of q (B, T, nq, hd) over k, v (B, T, nkv, hd),
    `block` rows of each per grid step.

    Returns (out (B, T, nq, hd) f32, lse (B, nkv, g, T) f32), the jnp
    path's `_flash_fwd_impl` outputs."""
    b, t, nq, hd = q.shape
    nkv = k.shape[2]
    g, nb = nq // nkv, t // block
    interpret = interpret_default() if interpret is None else interpret
    q_map, kv_map, lse_map, _ = _grid_maps(nkv, nb, jnp.minimum)
    out, lse = pl.pallas_call(
        functools.partial(_fwd_kernel, g=g, nb=nb, scale=1.0 / np.sqrt(hd)),
        grid=(b * nkv * nb, nb),
        in_specs=[pl.BlockSpec((None, g, block, hd), q_map),
                  pl.BlockSpec((None, None, block, hd), kv_map),
                  pl.BlockSpec((None, None, block, hd), kv_map)],
        out_specs=[pl.BlockSpec((None, g, block, hd), q_map),
                   pl.BlockSpec((None, None, g, block), lse_map)],
        out_shape=[jax.ShapeDtypeStruct((b, nq, t, hd), jnp.float32),
                   jax.ShapeDtypeStruct((b, nkv, g, t), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((g, block, 1), jnp.float32),
                        pltpu.VMEM((g, block, 1), jnp.float32)],
        compiler_params=compiler_params(_fwd_bytes(block, hd, g)),
        interpret=interpret,
        name="flash_attn_fwd",
    )(*(_heads_major(x, jnp.bfloat16) for x in (q, k, v)))
    return _heads_major(out), lse


# ---------------------------------------------------------------------------
# Backward: dK/dV, then dQ
# ---------------------------------------------------------------------------


def _dkv_kernel(q_ref, do_ref, k_ref, v_ref, lse_ref, d_ref, dk_ref, dv_ref,
                *, g, nb, scale):
    j = pl.program_id(0) % nb
    i = pl.program_id(1)

    @pl.when(i == 0)
    def _init():
        dk_ref[...] = jnp.zeros_like(dk_ref)
        dv_ref[...] = jnp.zeros_like(dv_ref)

    def step(masked):
        k, v = k_ref[...], v_ref[...]
        for h in range(g):
            q, do = q_ref[h], do_ref[h]
            st = _dot(k, q, _NT) * scale                     # (bt, bt)
            if masked:
                st = _causal(st, rows_are_q=False)
            pt = jnp.exp(st - lse_ref[h:h + 1, :])
            dv_ref[...] += _dot(pt, do, _NN)
            dpt = _dot(v, do, _NT)
            dst = pt * (dpt - d_ref[h:h + 1, :])
            dk_ref[...] += _dot(dst, q, _NN)

    pl.when(i == j)(lambda: step(True))
    pl.when(i > j)(lambda: step(False))

    @pl.when(i == nb - 1)
    def _epilogue():
        dk_ref[...] = dk_ref[...] * scale


def _dq_kernel(q_ref, do_ref, k_ref, v_ref, lse_ref, d_ref, dq_ref,
               lse_sc, d_sc, *, g, nb, scale):
    i = pl.program_id(0) % nb
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        for h in range(g):
            lse_sc[h] = _column(lse_ref[h:h + 1, :])
            d_sc[h] = _column(d_ref[h:h + 1, :])
        dq_ref[...] = jnp.zeros_like(dq_ref)

    def step(masked):
        k, v = k_ref[...], v_ref[...]
        for h in range(g):
            s = _dot(q_ref[h], k, _NT) * scale              # (bt, bt)
            if masked:
                s = _causal(s, rows_are_q=True)
            p = jnp.exp(s - lse_sc[h])
            dp = _dot(do_ref[h], v, _NT)
            ds = p * (dp - d_sc[h])
            dq_ref[h] += _dot(ds, k, _NN)

    pl.when(j < i)(lambda: step(False))
    pl.when(j == i)(lambda: step(True))

    @pl.when(j == nb - 1)
    def _epilogue():
        dq_ref[...] = dq_ref[...] * scale


def flash_bwd(q, k, v, out, lse, dout, block: int,
              interpret: Optional[bool] = None):
    """(dq, dk, dv), f32, in the shapes of q, k, v: the backward of
    `flash_fwd` given its residuals and the output's cotangent."""
    b, t, nq, hd = q.shape
    nkv = k.shape[2]
    g, nb = nq // nkv, t // block
    interpret = interpret_default() if interpret is None else interpret
    scale = 1.0 / np.sqrt(hd)

    # D_i = rowsum(dO * O), in lse's (B, nkv, g, T) layout
    dsum = jnp.sum(dout.astype(jnp.float32) * out.astype(jnp.float32),
                   axis=-1)
    dsum = jnp.transpose(dsum.reshape(b, t, nkv, g), (0, 2, 3, 1))
    args = tuple(_heads_major(x, jnp.bfloat16) for x in (q, dout, k, v)) \
        + (lse, dsum)
    grid = (b * nkv * nb, nb)
    q_spec = (None, g, block, hd)
    kv_spec = (None, None, block, hd)
    stat_spec = (None, None, g, block)

    # dK/dV: rows are KV blocks, query blocks innermost
    kv_rows, q_cols, _, stat_cols = _grid_maps(nkv, nb, jnp.maximum)
    dk, dv = pl.pallas_call(
        functools.partial(_dkv_kernel, g=g, nb=nb, scale=scale),
        grid=grid,
        in_specs=[pl.BlockSpec(q_spec, q_cols), pl.BlockSpec(q_spec, q_cols),
                  pl.BlockSpec(kv_spec, kv_rows),
                  pl.BlockSpec(kv_spec, kv_rows),
                  pl.BlockSpec(stat_spec, stat_cols),
                  pl.BlockSpec(stat_spec, stat_cols)],
        out_specs=[pl.BlockSpec(kv_spec, kv_rows),
                   pl.BlockSpec(kv_spec, kv_rows)],
        out_shape=[jax.ShapeDtypeStruct((b, nkv, t, hd), jnp.float32)] * 2,
        compiler_params=compiler_params(_dkv_bytes(block, hd, g)),
        interpret=interpret,
        name="flash_attn_dkv",
    )(*args)

    # dQ: rows are query blocks, KV blocks innermost
    q_rows, kv_cols, stat_rows, _ = _grid_maps(nkv, nb, jnp.minimum)
    dq = pl.pallas_call(
        functools.partial(_dq_kernel, g=g, nb=nb, scale=scale),
        grid=grid,
        in_specs=[pl.BlockSpec(q_spec, q_rows), pl.BlockSpec(q_spec, q_rows),
                  pl.BlockSpec(kv_spec, kv_cols),
                  pl.BlockSpec(kv_spec, kv_cols),
                  pl.BlockSpec(stat_spec, stat_rows),
                  pl.BlockSpec(stat_spec, stat_rows)],
        out_specs=pl.BlockSpec(q_spec, q_rows),
        out_shape=jax.ShapeDtypeStruct((b, nq, t, hd), jnp.float32),
        scratch_shapes=[pltpu.VMEM((g, block, 1), jnp.float32),
                        pltpu.VMEM((g, block, 1), jnp.float32)],
        compiler_params=compiler_params(_dq_bytes(block, hd, g)),
        interpret=interpret,
        name="flash_attn_dq",
    )(*args)

    return _heads_major(dq), _heads_major(dk), _heads_major(dv)
