"""User-facing wrapper for the streaming top-k decode kernel.

`pallas_topk(h, w, k)` mirrors `fused_ce.ops.pallas_loss`: callers may fix
the kernel tiling with an explicit `BlockPlan`; when they don't, the plan
is `choose_blocks` of the (rows, vocab, d) shape at the hidden states'
itemsize, resolved at trace time.

No custom VJP: sampling is not differentiated through.
"""

from __future__ import annotations

from typing import Optional

import jax

from repro.core.windows import BlockPlan, choose_blocks
from repro.kernels.sample_topk import kernel as K


def pallas_topk(
    h: jax.Array,
    w: jax.Array,
    k: int,
    *,
    valid_vocab: Optional[int] = None,
    logit_softcap: Optional[float] = None,
    plan: Optional[BlockPlan] = None,
    interpret: Optional[bool] = None,
    col_offset=0,
    w_scale: Optional[jax.Array] = None,
    allowed_mask: Optional[jax.Array] = None,
    return_lse: bool = False,
):
    """Top-k (values, global indices) of ``h @ w.T`` per row, logits-free.

    On non-TPU backends the kernel runs in interpret mode — bit-for-bit
    the same algorithm.  Output matches ``jax.lax.top_k`` of the masked
    dense logits exactly at every finite position, ties included (-inf
    tail positions, k > valid vocab, carry unspecified indices).

    `w_scale` (V,) marks `w` as row-quantized (`quantize_weight`).

    `allowed_mask` (B, V) constrains candidates to the nonzero-mask set
    (constrained decoding, DESIGN.md §12.3).  `return_lse=True` appends
    the per-row logsumexp (B,) over the same filtered logits (beam-search
    logprobs from one vocab scan).
    """
    if plan is None:
        plan = choose_blocks(h.shape[0], w.shape[0], h.shape[-1],
                             in_bytes=h.dtype.itemsize)
    return K.topk_scores(h, w, k, valid_vocab=valid_vocab,
                         logit_softcap=logit_softcap, plan=plan,
                         interpret=interpret, col_offset=col_offset,
                         w_scale=w_scale, allowed_mask=allowed_mask,
                         return_lse=return_lse)
