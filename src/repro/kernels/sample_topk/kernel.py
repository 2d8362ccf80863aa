"""Pallas TPU kernel: streaming per-row top-k of ``h @ W^T`` (DESIGN.md §5.3).

The decode-side sibling of the fused-CE forward (`kernels/fused_ce`): the
`(B, V)` logits tensor for a sampling step is never written to HBM.  The
kernel shares the fused-CE structure wholesale —

  * grid ``(R, Vb)`` with the vocab axis innermost and **sequential**
    ("arbitrary" dimension semantics), rows parallel;
  * the logits tile ``z = H_tile @ W_tile^T`` exists only in VMEM/VREGs,
    computed on the MXU with f32 accumulation and the optional tanh
    softcap applied in-tile;
  * the same masking convention: a column is valid iff it is structurally
    real (``local_col < V_orig``) and its global id (``local + offset``)
    is ``< valid_vocab``;
  * the same `BlockPlan` tiling, `choose_blocks` of the shape unless the
    caller passes a plan.

Instead of online-softmax scalars, the carried VMEM scratch is the running
per-row top-k — ``(block_rows, k_pad)`` values (f32) and global indices
(int32), sorted descending.  Each vocab step merges the logits tile into
that state with k extraction passes (max + tie-break-by-lowest-index, both
plain VPU reductions — no sort network, no `lax.top_k`, nothing Mosaic
can't lower).  Selection order makes the result bit-identical to
`jax.lax.top_k` of the masked dense logits at every FINITE position,
ties included: the carried state always holds lower global ids than the
current tile, state wins value ties, and within both state and tile the
lowest index wins.  Positions whose value is -inf (k exceeding the
valid vocabulary) carry unspecified indices.

The pure-JAX `serve/sampler.py:streaming_topk` is the semantic oracle
(`tests/test_sample_topk.py` holds the equivalence, hypothesis-driven).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.windows import _LANE, BlockPlan, choose_blocks
from repro.kernels.pallas_utils import compiler_params, interpret_default

_NEG_INF = float("-inf")
# sentinel > any global vocab id; used by the lowest-index tie-break scans
# (plain int — a jnp scalar here would be a captured constant in the kernel)
_BIG_IDX = 2 ** 30


def _topk_kernel(off_ref, h_ref, w_ref,          # inputs (+ opt. extras)
                 *rest,                          # [ws,][mask,] outs, scratch
                 k: int, valid: int, v_orig: int, bv: int, num_v: int,
                 softcap: Optional[float], quantized: bool,
                 masked: bool, want_lse: bool):
    rest = list(rest)
    ws_ref = rest.pop(0) if quantized else None
    mask_ref = rest.pop(0) if masked else None
    vals_ref, idx_ref = rest.pop(0), rest.pop(0)
    lse_ref = rest.pop(0) if want_lse else None
    vals_sc, idx_sc = rest.pop(0), rest.pop(0)
    m_sc, a_sc = (rest.pop(0), rest.pop(0)) if want_lse else (None, None)
    v = pl.program_id(1)

    @pl.when(v == 0)
    def _init():
        vals_sc[...] = jnp.full_like(vals_sc[...], _NEG_INF)
        idx_sc[...] = jnp.zeros_like(idx_sc[...])
        if want_lse:
            m_sc[...] = jnp.full_like(m_sc[...], _NEG_INF)
            a_sc[...] = jnp.zeros_like(a_sc[...])

    # (bm, bv) logits tile on the MXU, f32 accumulate; softcap in-tile.
    # A quantized W tile is cast in-register (int8/fp8 grids are exact in
    # bf16/f32) and the per-row scale factors out of the d-contraction:
    # the (1, bv) scale block multiplies the logits tile AFTER the dot,
    # so no dequantized W tile ever exists (DESIGN.md §10.2).
    wt = w_ref[...]
    if quantized:
        wt = wt.astype(h_ref.dtype)
    z = jax.lax.dot_general(
        h_ref[...], wt,
        dimension_numbers=(((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    if quantized:
        z = z * ws_ref[...]                      # (1, bv) broadcast
    if softcap is not None:
        cap = jnp.float32(softcap)
        z = cap * jnp.tanh(z / cap)
    bm = z.shape[0]
    local_col = v * bv + jax.lax.broadcasted_iota(jnp.int32, (bm, bv), 1)
    col = local_col + off_ref[0, 0]                        # global vocab id
    z = jnp.where((local_col < v_orig) & (col < valid), z, _NEG_INF)
    if masked:
        # constrained decoding: the (bm, bv) allowed-token tile zeroes
        # out disallowed columns before the top-k merge AND the softmax
        # accumulator — the scored distribution is the renormalized
        # allowed-set distribution (DESIGN.md §12.3)
        z = jnp.where(mask_ref[...] != 0, z, _NEG_INF)

    if want_lse:
        # online-softmax fold (fused-CE Alg. 1): lse over the same masked
        # candidate set the top-k selection sees
        m_prev = m_sc[...]                                   # (bm, 1)
        m_new = jnp.maximum(m_prev,
                            jnp.max(z, axis=1, keepdims=True))
        safe_m = jnp.where(jnp.isneginf(m_new), 0.0, m_new)
        a_sc[...] = (a_sc[...] * jnp.exp(m_prev - safe_m)
                     + jnp.sum(jnp.exp(z - safe_m), axis=1,
                               keepdims=True))
        m_sc[...] = m_new

    kp = vals_sc.shape[1]
    slot = jax.lax.broadcasted_iota(jnp.int32, (bm, kp), 1)

    def extract(j, carry):
        """Move the best remaining candidate of (state ∪ tile) to slot j."""
        z, state_v, new_v, new_i = carry
        # best remaining tile candidate; lowest global id wins value ties
        tmax = jnp.max(z, axis=1, keepdims=True)                  # (bm, 1)
        tcol = jnp.min(jnp.where(z == tmax, col, _BIG_IDX),
                       axis=1, keepdims=True)
        # best remaining state candidate; lowest slot == lowest global id
        smax = jnp.max(state_v, axis=1, keepdims=True)
        sslot = jnp.min(jnp.where(state_v == smax, slot, _BIG_IDX),
                        axis=1, keepdims=True)
        sidx = jnp.sum(jnp.where(slot == sslot, idx_sc[...], 0),
                       axis=1, keepdims=True)
        # state entries carry strictly lower ids than this tile, so the
        # state wins value ties (== lax.top_k's lowest-index-first order)
        take_state = smax >= tmax
        best_v = jnp.where(take_state, smax, tmax)
        best_i = jnp.where(take_state, sidx, tcol)
        write = slot == j
        new_v = jnp.where(write, best_v, new_v)
        new_i = jnp.where(write, best_i, new_i)
        # retire the winner from its source
        state_v = jnp.where(take_state & (slot == sslot), _NEG_INF, state_v)
        z = jnp.where(jnp.logical_not(take_state) & (col == tcol),
                      _NEG_INF, z)
        return z, state_v, new_v, new_i

    init = (z, vals_sc[...],
            jnp.full((bm, kp), _NEG_INF, jnp.float32),
            jnp.zeros((bm, kp), jnp.int32))
    _, _, new_v, new_i = jax.lax.fori_loop(0, k, extract, init)
    vals_sc[...] = new_v
    idx_sc[...] = new_i

    @pl.when(v == num_v - 1)
    def _epilogue():
        vals_ref[...] = new_v
        idx_ref[...] = new_i
        if want_lse:
            lse_ref[...] = m_sc[...] + jnp.log(a_sc[...])


def topk_scores(
    h: jax.Array, w: jax.Array, k: int, *,
    valid_vocab: Optional[int] = None,
    logit_softcap: Optional[float] = None,
    plan: Optional[BlockPlan] = None,
    interpret: Optional[bool] = None,
    col_offset=0,
    w_scale: Optional[jax.Array] = None,
    allowed_mask: Optional[jax.Array] = None,
    return_lse: bool = False,
):
    """Per-row top-k of ``h @ w.T`` via the streaming Pallas kernel.

    h: (B, d); w: (V, d).  Returns (values (B, k) f32, global indices
    (B, k) int32), sorted descending, bit-identical to ``jax.lax.top_k``
    of the masked dense logits at every finite position (ties break to
    the lowest index).  Rows and vocab are padded internally to the block
    plan; when k exceeds the valid vocabulary the tail positions hold
    ``-inf`` values and unspecified indices.

    `w_scale` (V,) f32 marks `w` as quantized (int8/fp8 per-row, see
    `kernels/quant.quantize_weight`): the kernel streams the 1-byte W
    tiles and rescales each logits tile in-register — half the HBM
    bytes per sampling step, no dequantized W anywhere.

    Tensor-parallel shards pass `col_offset` (global id of w's first row)
    and a global `valid_vocab`; per-shard (k-best values, ids) then merge
    with one small all-gather + host-side top-k — never the logits.

    `allowed_mask` (B, V) int8/bool constrains the candidate set: columns
    whose mask entry is 0 score -inf before both the top-k merge and the
    softmax accumulator (constrained/JSON decoding, DESIGN.md §12.3) —
    an all-ones mask is value-identical to no mask.  `return_lse=True`
    additionally returns the per-row logsumexp (B,) f32 over the same
    (validity- and mask-) filtered logits — one vocab scan yields both
    the candidates and their normalizer, so beam-search logprobs
    (``vals - lse[:, None]``) stay logits-free.
    """
    if k < 1:
        raise ValueError(f"top-k needs k >= 1, got {k}")
    n, d = h.shape
    v_orig = w.shape[0]
    valid = v_orig if valid_vocab is None else valid_vocab
    plan = plan or choose_blocks(n, v_orig, d, in_bytes=w.dtype.itemsize)
    bm, bv = plan.block_rows, plan.block_v
    interpret = interpret_default() if interpret is None else interpret
    kp = -(-k // _LANE) * _LANE                     # lane-aligned state
    quantized = w_scale is not None
    masked = allowed_mask is not None

    n_pad = (-n) % bm
    v_pad = (-v_orig) % bv
    if n_pad:
        h = jnp.pad(h, ((0, n_pad), (0, 0)))
    if v_pad:
        w = jnp.pad(w, ((0, v_pad), (0, 0)))
    np_, vp = h.shape[0], w.shape[0]
    num_r, num_v = np_ // bm, vp // bv

    off = jnp.asarray(col_offset, jnp.int32).reshape(1, 1)
    kern = functools.partial(_topk_kernel, k=k, valid=valid, v_orig=v_orig,
                             bv=bv, num_v=num_v, softcap=logit_softcap,
                             quantized=quantized, masked=masked,
                             want_lse=return_lse)
    in_specs = [
        pl.BlockSpec((1, 1), lambda r, v: (0, 0)),      # col offset
        pl.BlockSpec((bm, d), lambda r, v: (r, 0)),     # h
        pl.BlockSpec((bv, d), lambda r, v: (v, 0)),     # w
    ]
    inputs = [off, h, w]
    if quantized:
        ws = jnp.pad(w_scale.astype(jnp.float32), (0, v_pad))[None, :]
        in_specs.append(pl.BlockSpec((1, bv), lambda r, v: (0, v)))
        inputs.append(ws)
    if masked:
        if allowed_mask.shape != (n, v_orig):
            raise ValueError(f"allowed_mask shape {allowed_mask.shape} "
                             f"!= (rows, vocab) ({n}, {v_orig})")
        am = jnp.pad(allowed_mask.astype(jnp.int8),
                     ((0, n_pad), (0, v_pad)))
        in_specs.append(pl.BlockSpec((bm, bv), lambda r, v: (r, v)))
        inputs.append(am)
    out_spec = pl.BlockSpec((bm, kp), lambda r, v: (r, 0))
    out_specs = [out_spec, out_spec]
    out_shape = [jax.ShapeDtypeStruct((np_, kp), jnp.float32),
                 jax.ShapeDtypeStruct((np_, kp), jnp.int32)]
    scratch = [pltpu.VMEM((bm, kp), jnp.float32),
               pltpu.VMEM((bm, kp), jnp.int32)]
    if return_lse:
        out_specs.append(pl.BlockSpec((bm, 1), lambda r, v: (r, 0)))
        out_shape.append(jax.ShapeDtypeStruct((np_, 1), jnp.float32))
        scratch += [pltpu.VMEM((bm, 1), jnp.float32),
                    pltpu.VMEM((bm, 1), jnp.float32)]
    out = pl.pallas_call(
        kern,
        grid=(num_r, num_v),
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=scratch,
        compiler_params=compiler_params(),
        interpret=interpret,
        name="sample_topk",
    )(*inputs)
    vals, idxs = out[0][:n, :k], out[1][:n, :k]
    if return_lse:
        return vals, idxs, out[2][:n, 0]
    return vals, idxs
