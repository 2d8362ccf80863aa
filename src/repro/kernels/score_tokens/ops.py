"""User-facing wrapper for the token-scoring kernel.

`pallas_score_tokens(h, w, ids)` mirrors `sample_topk.ops.pallas_topk`:
callers may fix the tiling with an explicit `BlockPlan`; when they
don't, the plan is `choose_blocks` of the (rows, vocab, d) shape at the
hidden states' itemsize, resolved at trace time.

No custom VJP: scoring/verification is not differentiated through.
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core.windows import BlockPlan, choose_blocks
from repro.kernels.score_tokens import kernel as K


def pallas_score_tokens(
    h: jax.Array,
    w: jax.Array,
    ids: jax.Array,
    *,
    valid_vocab: Optional[int] = None,
    logit_softcap: Optional[float] = None,
    temperature: Optional[float] = None,
    plan: Optional[BlockPlan] = None,
    interpret: Optional[bool] = None,
    col_offset=0,
    w_scale: Optional[jax.Array] = None,
) -> Tuple[jax.Array, jax.Array]:
    """(logp (N, P) f32, lse (N,) f32) of candidate ids — logits-free.

    `w_scale` (V,) marks `w` as row-quantized (`quantize_weight`): the
    kernel streams 1-byte W tiles with in-register rescale.

    ``logp[r, p] = log softmax(h_r @ w.T)[ids[r, p]]`` on the valid
    vocabulary (softcap, then 1/T temperature scaling, applied inside
    the scan when given — the distribution the sampler actually draws
    from); ids outside ``[0, valid_vocab)`` score -inf.  On non-TPU
    backends the kernel runs in interpret mode — bit-for-bit the same
    algorithm.

    Tensor-parallel shards pass `col_offset` and a global `valid_vocab`,
    psum the raw candidate logits and logsumexp-merge per-shard lse
    (see `kernel.score_stats`); single-device callers get logp directly.
    """
    squeeze = ids.ndim == 1
    if squeeze:
        ids = ids[:, None]
    if plan is None:
        plan = choose_blocks(h.shape[0], w.shape[0], h.shape[-1],
                             in_bytes=h.dtype.itemsize)
    lse, zt = K.score_stats(h, w, ids, valid_vocab=valid_vocab,
                            logit_softcap=logit_softcap,
                            temperature=temperature, plan=plan,
                            interpret=interpret, col_offset=col_offset,
                            w_scale=w_scale)
    valid = w.shape[0] if valid_vocab is None else valid_vocab
    ok = (ids >= 0) & (ids < valid)
    logp = jnp.where(ok, zt - lse[:, None], -jnp.inf)
    if squeeze:
        logp = logp[:, 0]
    return logp, lse
