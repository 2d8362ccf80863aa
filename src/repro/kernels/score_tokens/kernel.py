"""Pallas TPU kernel: log-probabilities of candidate tokens, logits-free.

The verification side of speculative decoding (DESIGN.md §6.2) and a
general loglikelihood / perplexity scoring primitive: for each row `r`
with hidden state `h_r` and P candidate token ids `ids_r`, compute

    logp[r, p] = z[r, ids[r, p]] - logsumexp_c z[r, c],   z = h @ W^T

without ever materializing the `(N, V)` logits.  This is exactly the
fused-CE forward's gather-under-online-softmax (paper Alg. 1 / the Cut
Your Losses trick) with P gathered columns per row instead of one:

  * grid ``(R, Vb)``, vocab innermost and **sequential** ("arbitrary"
    dimension semantics), rows parallel — the fused-CE layout;
  * the logits tile ``z = H_tile @ W_tile^T`` exists only in VMEM/VREGs
    (MXU, f32 accumulation), optional tanh softcap applied in-tile;
  * the carried VMEM scratch per row tile is the online-softmax state
    ``(m, a)`` — (bm, 1) f32 each — plus the candidate-logit accumulator
    ``zt`` of shape (bm, P_pad);
  * each vocab step folds the tile into (m, a) exactly as fused-CE does
    and runs P gather passes (mask + row-sum, plain VPU reductions —
    nothing Mosaic can't lower) to pick candidate logits out of the tile;
  * the same masking convention: a column is structurally real iff
    ``local_col < V_orig`` and globally valid iff ``local + offset <
    valid_vocab``.

Candidate ids that appear in no valid column contribute 0 to ``zt`` —
the ops wrapper masks their logp to -inf.  Tensor-parallel shards pass
`col_offset`/`total_valid` and psum ``zt`` / logsumexp-merge ``lse``
across shards (ids stay global), mirroring `fused_ce.fwd_stats`.

`ref.streaming_score` is the pure-JAX semantic oracle
(`tests/test_score_tokens.py` holds the equivalence).
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.windows import _LANE, BlockPlan, choose_blocks
from repro.kernels.pallas_utils import compiler_params, interpret_default

_NEG_INF = float("-inf")
# pad value for candidate slots beyond P: never equals a global column id
_NO_ID = -1


def _score_kernel(off_ref, ids_ref, h_ref, w_ref,   # inputs (+ opt. scale)
                  *rest,                            # [ws_ref,] outs, scratch
                  n_cand: int, valid: int, v_orig: int, bv: int,
                  num_v: int, softcap: Optional[float], inv_temp: float,
                  quantized: bool):
    if quantized:
        ws_ref, lse_ref, zt_ref, m_sc, a_sc, zt_sc = rest
    else:
        lse_ref, zt_ref, m_sc, a_sc, zt_sc = rest
        ws_ref = None
    v = pl.program_id(1)

    @pl.when(v == 0)
    def _init():
        m_sc[...] = jnp.full_like(m_sc[...], _NEG_INF)
        a_sc[...] = jnp.zeros_like(a_sc[...])
        zt_sc[...] = jnp.zeros_like(zt_sc[...])

    # (bm, bv) logits tile on the MXU, f32 accumulate; softcap and
    # temperature applied in-tile (sampling order: cap, then z/T).
    # Quantized W: cast the 1-byte tile in-register (lossless), rescale
    # the logits tile by the (1, bv) per-row scales BEFORE the softcap —
    # the scale is part of the raw logit (DESIGN.md §10.2).
    wt = w_ref[...]
    if quantized:
        wt = wt.astype(h_ref.dtype)
    z = jax.lax.dot_general(
        h_ref[...], wt,
        dimension_numbers=(((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    if quantized:
        z = z * ws_ref[...]
    if softcap is not None:
        cap = jnp.float32(softcap)
        z = cap * jnp.tanh(z / cap)
    if inv_temp != 1.0:
        z = z * jnp.float32(inv_temp)
    bm = z.shape[0]
    local_col = v * bv + jax.lax.broadcasted_iota(jnp.int32, (bm, bv), 1)
    col = local_col + off_ref[0, 0]                      # global vocab id
    col_valid = (local_col < v_orig) & (col < valid)
    z_masked = jnp.where(col_valid, z, _NEG_INF)

    # online max / accumulator update (fused-CE Alg. 1 lines 8-14)
    m_prev = m_sc[...]                                   # (bm, 1)
    m_new = jnp.maximum(m_prev, jnp.max(z_masked, axis=1, keepdims=True))
    safe_m = jnp.where(jnp.isneginf(m_new), 0.0, m_new)
    a_sc[...] = (a_sc[...] * jnp.exp(m_prev - safe_m)
                 + jnp.sum(jnp.exp(z_masked - safe_m), axis=1,
                           keepdims=True))
    m_sc[...] = m_new

    # candidate-logit gathers: one VPU pass per candidate slot.  The
    # col_valid guard keeps local pad columns (which alias other shards'
    # global ids) and invalid-vocab columns out of the gather.
    ids = ids_ref[...]                                   # (bm, P_pad) int32
    kp = ids.shape[1]
    pslot = jax.lax.broadcasted_iota(jnp.int32, (bm, kp), 1)

    def gather(p, zt):
        idp = jnp.sum(jnp.where(pslot == p, ids, 0), axis=1,
                      keepdims=True)                     # (bm, 1)
        contrib = jnp.sum(jnp.where((col == idp) & col_valid, z, 0.0),
                          axis=1, keepdims=True)
        return zt + jnp.where(pslot == p, contrib, 0.0)

    zt_sc[...] = jax.lax.fori_loop(0, n_cand, gather, zt_sc[...])

    @pl.when(v == num_v - 1)
    def _epilogue():
        lse_ref[...] = m_sc[...] + jnp.log(a_sc[...])
        zt_ref[...] = zt_sc[...]


def score_stats(
    h: jax.Array, w: jax.Array, ids: jax.Array, *,
    valid_vocab: Optional[int] = None,
    logit_softcap: Optional[float] = None,
    temperature: Optional[float] = None,
    plan: Optional[BlockPlan] = None,
    interpret: Optional[bool] = None,
    col_offset=0,
    w_scale: Optional[jax.Array] = None,
) -> Tuple[jax.Array, jax.Array]:
    """Per-row (lse, candidate logits) via the streaming Pallas kernel.

    `w_scale` (V,) f32 marks `w` as row-quantized (int8/fp8, see
    `kernels/quant.quantize_weight`): W tiles stream at 1 byte/element
    and each logits tile is rescaled in-register before softcap/T.

    h: (N, d); w: (V, d); ids: (N,) or (N, P) int32 global token ids.
    Returns (lse (N,) f32, z_cand (N, P) f32) where ``z_cand[r, p]`` is
    the (softcapped, temperature-scaled, masked) logit of token
    ``ids[r, p]`` — 0.0 when the id matches no valid column of this
    shard (callers mask, or psum across shards).  ``logp = z_cand -
    lse[:, None]`` on one device.  `temperature` > 0 scales logits by
    1/T AFTER the softcap, matching the sampler's order, so the scored
    distribution is the one actually sampled from; None or <= 0 scores
    unscaled (T = 1).
    """
    if ids.ndim == 1:
        ids = ids[:, None]
    n, d = h.shape
    p_cand = ids.shape[1]
    if ids.shape[0] != n:
        raise ValueError(f"ids rows {ids.shape[0]} != h rows {n}")
    v_orig = w.shape[0]
    valid = v_orig if valid_vocab is None else valid_vocab
    plan = plan or choose_blocks(n, v_orig, d, in_bytes=w.dtype.itemsize)
    bm, bv = plan.block_rows, plan.block_v
    interpret = interpret_default() if interpret is None else interpret
    kp = -(-p_cand // _LANE) * _LANE                 # lane-aligned cands
    quantized = w_scale is not None

    n_pad = (-n) % bm
    v_pad = (-v_orig) % bv
    if n_pad:
        h = jnp.pad(h, ((0, n_pad), (0, 0)))
    if v_pad:
        w = jnp.pad(w, ((0, v_pad), (0, 0)))
    ids = jnp.pad(ids.astype(jnp.int32),
                  ((0, n_pad), (0, kp - p_cand)),
                  constant_values=_NO_ID)
    np_, vp = h.shape[0], w.shape[0]
    num_r, num_v = np_ // bm, vp // bv

    inv_temp = (1.0 / float(temperature)
                if temperature is not None and temperature > 0 else 1.0)
    off = jnp.asarray(col_offset, jnp.int32).reshape(1, 1)
    kern = functools.partial(_score_kernel, n_cand=p_cand, valid=valid,
                             v_orig=v_orig, bv=bv, num_v=num_v,
                             softcap=logit_softcap, inv_temp=inv_temp,
                             quantized=quantized)
    in_specs = [
        pl.BlockSpec((1, 1), lambda r, v: (0, 0)),      # col offset
        pl.BlockSpec((bm, kp), lambda r, v: (r, 0)),    # candidate ids
        pl.BlockSpec((bm, d), lambda r, v: (r, 0)),     # h
        pl.BlockSpec((bv, d), lambda r, v: (v, 0)),     # w
    ]
    inputs = [off, ids, h, w]
    if quantized:
        ws = jnp.pad(w_scale.astype(jnp.float32), (0, v_pad))[None, :]
        in_specs.append(pl.BlockSpec((1, bv), lambda r, v: (0, v)))
        inputs.append(ws)
    lse, zt = pl.pallas_call(
        kern,
        grid=(num_r, num_v),
        in_specs=in_specs,
        out_specs=[pl.BlockSpec((bm, 1), lambda r, v: (r, 0)),
                   pl.BlockSpec((bm, kp), lambda r, v: (r, 0))],
        out_shape=[jax.ShapeDtypeStruct((np_, 1), jnp.float32),
                   jax.ShapeDtypeStruct((np_, kp), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((bm, 1), jnp.float32),
                        pltpu.VMEM((bm, 1), jnp.float32),
                        pltpu.VMEM((bm, kp), jnp.float32)],
        compiler_params=compiler_params(),
        interpret=interpret,
        name="score_tokens",
    )(*inputs)
    return lse[:n, 0], zt[:n, :p_cand]
