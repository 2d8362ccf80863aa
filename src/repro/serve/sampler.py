"""Streaming samplers: next-token selection WITHOUT materializing logits.

The serving-side twin of the paper's idea (and of its Online-Softmax+TopK
related work): the (B, V) logits tensor for a decode step is never formed.
Two implementations share one contract:

  * `streaming_topk` — pure JAX: scans the lm_head in vocab chunks via
    `lax.scan`, keeping a running (values, indices) top-k.  Runs on any
    backend; serves as the semantic oracle for the kernel.
  * `repro.kernels.sample_topk.pallas_topk` — the Pallas TPU kernel with
    the same VMEM online-scan structure and BlockPlan tiling as the
    fused-CE forward (DESIGN.md §5.3).  Bit-identical to the oracle at
    every finite position, ties included.

`sample_tokens` draws greedy (temperature == 0) or temperature/top-k/
top-p samples from the surviving k logits.  `logit_softcap` applies the
Gemma-style tanh cap INSIDE the vocab scan — sampling from uncapped
logits while the model trained with capped ones is a distribution
mismatch (the softcap is monotonic, so greedy decode is unaffected, but
temperature/top-p sampling is not).

Memory: O(B * (block_v + k)) instead of O(B * V) — at B=128, V=262144
that is ~130 MB of logits avoided per step.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp


def streaming_topk(
    h: jax.Array, w: jax.Array, k: int, *,
    block_v: int = 8192, valid_vocab: Optional[int] = None,
    logit_softcap: Optional[float] = None,
    w_scale: Optional[jax.Array] = None,
    allowed_mask: Optional[jax.Array] = None,
    return_lse: bool = False,
):
    """Top-k of h @ w.T per row, streamed over vocab chunks.

    h: (B, d); w: (V, d).  Returns (values (B, k) f32, indices (B, k)).
    `w_scale` (V,) marks `w` as row-quantized (`kernels/quant`): each
    chunk's logits are rescaled after the dot, so only one (B, bv)
    chunk of dequantized math lives at a time.

    `allowed_mask` (B, V) restricts candidates to the nonzero-mask set
    (constrained decoding: disallowed columns score -inf before the
    merge); `return_lse=True` appends the per-row logsumexp (B,) over
    the same filtered logits — the semantic oracle for the kernel's
    masked / beam-scoring variants.
    """
    b, d = h.shape
    v = w.shape[0]
    valid = v if valid_vocab is None else valid_vocab
    bv = min(block_v, v)
    pad = (-v) % bv
    if pad:
        w = jnp.pad(w, ((0, pad), (0, 0)))
    n_chunks = w.shape[0] // bv
    w_chunks = w.reshape(n_chunks, bv, d)
    s_chunks = None
    if w_scale is not None:
        s_chunks = jnp.pad(w_scale.astype(jnp.float32),
                           (0, pad)).reshape(n_chunks, bv)
    m_chunks = None
    if allowed_mask is not None:
        m_chunks = jnp.pad(allowed_mask.astype(jnp.int8),
                           ((0, 0), (0, pad)))
        m_chunks = m_chunks.reshape(b, n_chunks, bv).transpose(1, 0, 2)
    h32 = h.astype(jnp.float32)

    def body(carry, inputs):
        best_v, best_i, m, a = carry
        w_chunk, s_chunk, m_chunk, idx = inputs
        z = jnp.dot(h32, w_chunk.T.astype(jnp.float32),
                    preferred_element_type=jnp.float32)   # (B, bv)
        if s_chunk is not None:
            z = z * s_chunk[None, :]
        if logit_softcap is not None:
            cap = jnp.float32(logit_softcap)
            z = cap * jnp.tanh(z / cap)
        col = idx * bv + jnp.arange(bv, dtype=jnp.int32)
        z = jnp.where(col[None, :] < valid, z, -jnp.inf)
        if m_chunk is not None:
            z = jnp.where(m_chunk != 0, z, -jnp.inf)
        if return_lse:
            m_new = jnp.maximum(m, jnp.max(z, axis=1, keepdims=True))
            safe_m = jnp.where(jnp.isneginf(m_new), 0.0, m_new)
            a = (a * jnp.exp(m - safe_m)
                 + jnp.sum(jnp.exp(z - safe_m), axis=1, keepdims=True))
            m = m_new
        # a chunk contributes at most bv candidates, so clamp the chunk
        # top-k there (k > block_v is legal: the merge keeps k overall)
        cv, ci = jax.lax.top_k(z, min(k, bv))
        ci = jnp.take(col, ci)
        merged_v = jnp.concatenate([best_v, cv], axis=1)
        merged_i = jnp.concatenate([best_i, ci], axis=1)
        mv, sel = jax.lax.top_k(merged_v, k)
        mi = jnp.take_along_axis(merged_i, sel, axis=1)
        return (mv, mi, m, a), None

    init = (jnp.full((b, k), -jnp.inf, jnp.float32),
            jnp.zeros((b, k), jnp.int32),
            jnp.full((b, 1), -jnp.inf, jnp.float32),
            jnp.zeros((b, 1), jnp.float32))
    chunk_ids = jnp.arange(n_chunks, dtype=jnp.int32)

    xs = [w_chunks, chunk_ids]
    unpack = [0, None, None, 1]        # (w, scale, mask, idx) positions
    if s_chunks is not None:
        unpack[1] = len(xs)
        xs.append(s_chunks)
    if m_chunks is not None:
        unpack[2] = len(xs)
        xs.append(m_chunks)

    def step(c, packed):
        return body(c, tuple(None if i is None else packed[i]
                             for i in unpack))

    (vals, idxs, m, a), _ = jax.lax.scan(step, init, tuple(xs))
    if return_lse:
        return vals, idxs, (m + jnp.log(a))[:, 0]
    return vals, idxs


def top_p_mask(logits: jax.Array, top_p: float) -> jax.Array:
    """Nucleus filter over DESCENDING-sorted logits: keep the smallest
    prefix whose probability mass reaches `top_p`, -inf the rest.

    Both `streaming_topk` and `pallas_topk` return values sorted
    descending, so no extra sort is needed.  The top-1 token is always
    kept (`cum - probs < top_p` holds at position 0 for any top_p > 0),
    and ``top_p >= 1`` is exactly the identity — without the short
    circuit, f32 cumsum rounding can push ``cum - probs`` of a tail
    token to 1.0 and silently drop it.
    """
    if top_p >= 1.0:
        return logits
    probs = jax.nn.softmax(logits, axis=-1)
    cum = jnp.cumsum(probs, axis=-1)
    keep = (cum - probs) < jnp.float32(top_p)
    return jnp.where(keep, logits, -jnp.inf)


def sample_tokens(
    h: jax.Array, w: jax.Array, rng: jax.Array, *,
    temperature: float = 0.0, top_k: int = 40,
    top_p: Optional[float] = None,
    block_v: int = 8192, valid_vocab: Optional[int] = None,
    logit_softcap: Optional[float] = None,
    impl: str = "pallas",
    w_scale: Optional[jax.Array] = None,
    allowed_mask: Optional[jax.Array] = None,
) -> jax.Array:
    """Next-token ids (B,) — greedy when temperature == 0.

    impl: 'pallas' (streaming Pallas kernel, interpret mode off-TPU) or
    'jax' (the pure-JAX `streaming_topk` oracle); the kernel tiles by
    `choose_blocks` of the shape.  `w_scale` marks `w` as a row-quantized
    lm_head (`ServeConfig.head_dtype`).
    `allowed_mask` (B, V) restricts sampling to the nonzero-mask token
    set per row (constrained/JSON decoding): disallowed tokens score
    -inf inside the vocab scan and can never be drawn at any temperature
    or top_p; an all-ones mask is token-identical to no mask.
    """
    k = 1 if temperature == 0.0 else top_k
    if impl == "pallas":
        from repro.kernels.sample_topk import pallas_topk
        vals, idxs = pallas_topk(h, w, k, valid_vocab=valid_vocab,
                                 logit_softcap=logit_softcap,
                                 w_scale=w_scale,
                                 allowed_mask=allowed_mask)
    elif impl == "jax":
        vals, idxs = streaming_topk(h, w, k, block_v=block_v,
                                    valid_vocab=valid_vocab,
                                    logit_softcap=logit_softcap,
                                    w_scale=w_scale,
                                    allowed_mask=allowed_mask)
    else:
        raise ValueError(f"unknown sampler impl {impl!r}")
    if temperature == 0.0:
        return idxs[:, 0]
    logits = vals / jnp.float32(temperature)
    if top_p is not None:
        logits = top_p_mask(logits, top_p)
    choice = jax.random.categorical(rng, logits, axis=-1)   # (B,)
    return jnp.take_along_axis(idxs, choice[:, None], axis=1)[:, 0]
