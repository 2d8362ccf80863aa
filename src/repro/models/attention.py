"""GQA attention: blockwise (memory-bounded) training/prefill + cached decode.

Training/prefill uses an online-softmax *blockwise* attention (FlashAttention
recurrence expressed in jax.lax): the score matrix exists only one
(chunk_q x chunk_k) tile at a time, bounding activation memory to
O(T * chunk) instead of O(T^2).  Causal problems iterate only the lower-
triangular KV blocks via a dynamic `fori_loop` bound; local-window problems
slice just the in-window KV band per query block.

This reuses the same online (m, a) machinery as the paper's fused loss —
the repo's unifying numeric primitive.

Decode uses the KV cache with a single masked einsum (q_len == 1: scores are
O(S), no tiling needed).

The training call (`attention_layer` without a cache) takes the Pallas
flash-attention kernels of `kernels/flash_attn` instead of the jnp loops
where they apply (`_kernel_block`): on a TPU, causal, no window or
softcap, unsharded, head_dim and T on the kernels' 128-wide tiling.  The
serving prefills always keep the jnp recurrence: `extend_attention` is
bit-identical to it, and that contract holds for the jnp path only.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.kernels import flash_attn
from repro.models import layers as L

_NEG_INF = float("-inf")


@dataclasses.dataclass(frozen=True)
class AttnConfig:
    d_model: int
    num_heads: int
    num_kv_heads: int
    head_dim: int
    qkv_bias: bool = False
    qk_norm: bool = False
    rope_theta: float = 10000.0
    attn_softcap: Optional[float] = None
    window: Optional[int] = None          # local attention window (Griffin)
    causal: bool = True
    chunk_q: int = 512
    chunk_k: int = 1024
    n_layers_scale: int = 1
    # paged-KV decode implementation: 'jax' (gather + decode_attention,
    # the oracle) or 'pallas' (kernels/paged_attn, never materializes
    # the gathered cache).  Only consulted when the cache dict is paged.
    paged_impl: str = "jax"


def init_attention(key, cfg: AttnConfig, dtype=jnp.float32):
    ks = jax.random.split(key, 4)
    d, nq, nkv, hd = cfg.d_model, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    out_scale = 1.0 / np.sqrt(2.0 * max(cfg.n_layers_scale, 1))
    p = {
        "wq": L.dense_init(ks[0], (d, nq, hd), dtype=dtype),
        "wk": L.dense_init(ks[1], (d, nkv, hd), dtype=dtype),
        "wv": L.dense_init(ks[2], (d, nkv, hd), dtype=dtype),
        "wo": L.dense_init(ks[3], (nq, hd, d), scale=out_scale, dtype=dtype),
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((nq, hd), dtype)
        p["bk"] = jnp.zeros((nkv, hd), dtype)
        p["bv"] = jnp.zeros((nkv, hd), dtype)
    if cfg.qk_norm:
        p["q_norm"] = jnp.ones((hd,), dtype)
        p["k_norm"] = jnp.ones((hd,), dtype)
    return p


def _project_qkv(params, x, positions, cfg: AttnConfig):
    q = jnp.einsum("btd,dnh->btnh", x, params["wq"])
    k = jnp.einsum("btd,dnh->btnh", x, params["wk"])
    v = jnp.einsum("btd,dnh->btnh", x, params["wv"])
    if cfg.qkv_bias:
        q, k, v = q + params["bq"], k + params["bk"], v + params["bv"]
    if cfg.qk_norm:
        q = L.head_rmsnorm(params["q_norm"], q)
        k = L.head_rmsnorm(params["k_norm"], k)
    cos, sin = L.rope_angles(positions, cfg.head_dim, cfg.rope_theta)
    q = L.apply_rope(q, cos, sin)
    k = L.apply_rope(k, cos, sin)
    return q, k, v


def _tile_scores(qb, kb, cfg: AttnConfig):
    """(B, cq, nkv, g, hd) x (B, ck, nkv, hd) -> (B, nkv, g, cq, ck) f32."""
    s = jnp.einsum("bqngh,bknh->bngqk", qb, kb,
                   preferred_element_type=jnp.float32)
    s = s * (1.0 / np.sqrt(cfg.head_dim))
    if cfg.attn_softcap is not None:
        cap = jnp.float32(cfg.attn_softcap)
        s = cap * jnp.tanh(s / cap)
    return s


def _pad_axis1(x, pad):
    return jnp.pad(x, ((0, 0), (0, pad)) + ((0, 0),) * (x.ndim - 2)) \
        if pad else x


def _block_mask(qpos, kpos, kv_len, cfg: AttnConfig):
    mask = (kpos[None, :] < kv_len)
    if cfg.causal:
        mask = mask & (kpos[None, :] <= qpos[:, None])
    if cfg.window is not None:
        mask = mask & (kpos[None, :] > qpos[:, None] - cfg.window)
    return mask


def _kv_bounds(qi, cq, ck, nkb, tk_p, cfg: AttnConfig):
    """KV-block range visible from query block qi (traced bounds OK)."""
    if cfg.causal:
        hi = jnp.minimum(((qi + 1) * cq + ck - 1) // ck, nkb)
    else:
        hi = nkb
    if cfg.window is not None:
        lo = jnp.maximum((qi * cq - cfg.window) // ck, 0)
    else:
        lo = 0
    return lo, hi


def _q_bounds(kj, cq, ck, nqb, cfg: AttnConfig):
    """Query-block range that can see kv block kj."""
    if cfg.causal:
        lo = (kj * ck) // cq
    else:
        lo = 0
    if cfg.window is not None:
        hi = jnp.minimum((kj * ck + ck + cfg.window + cq - 1) // cq, nqb)
    else:
        hi = nqb
    return lo, hi


def _flash_fwd_impl(q, k, v, cfg: AttnConfig, kv_len: int):
    """Returns (out (B,Tq,nq,hd) f32, lse (B,nkv,g,Tq) f32)."""
    b, tq_p, nq, hd = q.shape
    tk_p, nkv = k.shape[1], k.shape[2]
    g = nq // nkv
    cq, ck = min(cfg.chunk_q, tq_p), min(cfg.chunk_k, tk_p)
    nqb, nkb = tq_p // cq, tk_p // ck
    q5 = q.reshape(b, nqb, cq, nkv, g, hd)

    def per_q_block(qi):
        qb = q5[:, qi]                                   # (B, cq, nkv, g, hd)
        qpos = qi * cq + jnp.arange(cq)

        def kv_step(kj, carry):
            m, a, acc = carry
            kb = jax.lax.dynamic_slice_in_dim(k, kj * ck, ck, axis=1)
            vb = jax.lax.dynamic_slice_in_dim(v, kj * ck, ck, axis=1)
            s = _tile_scores(qb, kb, cfg)                # (B,nkv,g,cq,ck)
            kpos = kj * ck + jnp.arange(ck)
            mask = _block_mask(qpos, kpos, kv_len, cfg)
            s = jnp.where(mask[None, None, None], s, _NEG_INF)
            m_new = jnp.maximum(m, jnp.max(s, axis=-1))
            m_safe = jnp.where(jnp.isneginf(m_new), 0.0, m_new)
            p = jnp.exp(s - m_safe[..., None])
            scale_prev = jnp.exp(m - m_safe)
            a = a * scale_prev + jnp.sum(p, axis=-1)
            pv = jnp.einsum("bngqk,bknh->bngqh", p.astype(vb.dtype), vb,
                            preferred_element_type=jnp.float32)
            acc = acc * scale_prev[..., None] + pv
            return m_new, a, acc

        init = (
            jnp.full((b, nkv, g, cq), _NEG_INF, jnp.float32),
            jnp.zeros((b, nkv, g, cq), jnp.float32),
            jnp.zeros((b, nkv, g, cq, hd), jnp.float32),
        )
        lo, hi = _kv_bounds(qi, cq, ck, nkb, tk_p, cfg)
        m, a, acc = jax.lax.fori_loop(lo, hi, kv_step, init)
        a_safe = jnp.maximum(a, 1e-30)
        out = acc / a_safe[..., None]
        m_fin = jnp.where(jnp.isneginf(m), 0.0, m)
        lse = m_fin + jnp.log(a_safe)                    # (B,nkv,g,cq)
        return jnp.transpose(out, (0, 3, 1, 2, 4)), lse

    outs, lses = jax.lax.map(per_q_block, jnp.arange(nqb))
    out = jnp.transpose(outs, (1, 0, 2, 3, 4, 5)).reshape(b, tq_p, nq, hd)
    lse = jnp.moveaxis(lses, 0, 3).reshape(b, nkv, g, tq_p)
    return out, lse


def _flash_bwd_impl(q, k, v, out, lse, dout, cfg: AttnConfig, kv_len: int):
    """FlashAttention-style backward: recompute score tiles blockwise.

    All tensors padded to block multiples; f32 throughout.
    """
    b, tq_p, nq, hd = q.shape
    tk_p, nkv = k.shape[1], k.shape[2]
    g = nq // nkv
    cq, ck = min(cfg.chunk_q, tq_p), min(cfg.chunk_k, tk_p)
    nqb, nkb = tq_p // cq, tk_p // ck
    scale = 1.0 / np.sqrt(cfg.head_dim)

    q5 = q.reshape(b, nqb, cq, nkv, g, hd)
    do5 = dout.reshape(b, nqb, cq, nkv, g, hd)
    # D_i = rowsum(dout * out)
    dsum = jnp.sum(dout.astype(jnp.float32) * out.astype(jnp.float32),
                   axis=-1)                               # (B, Tq, nq)
    dsum = dsum.reshape(b, nqb, cq, nkv, g)
    lse5 = jnp.moveaxis(lse.reshape(b, nkv, g, nqb, cq), 3, 1)

    def _tile(qb, kb, qpos, kpos):
        """p (softmax tile) and the d(s_capped)->d(s_raw) chain factor."""
        s = _tile_scores(qb, kb, cfg)                     # capped scores
        mask = _block_mask(qpos, kpos, kv_len, cfg)
        s_m = jnp.where(mask[None, None, None], s, _NEG_INF)
        return s, s_m, mask

    # ---------------- dQ ----------------
    def per_q_block(qi):
        qb = q5[:, qi]
        dob = do5[:, qi].astype(jnp.float32)
        dob = jnp.transpose(dob, (0, 2, 3, 1, 4))         # (B,nkv,g,cq,hd)
        lse_b = lse5[:, qi][..., None]                    # (B,nkv,g,cq,1)
        ds_b = dsum[:, qi]
        ds_b = jnp.transpose(ds_b, (0, 2, 3, 1))[..., None]
        qpos = qi * cq + jnp.arange(cq)

        def kv_step(kj, dq_acc):
            kb = jax.lax.dynamic_slice_in_dim(k, kj * ck, ck, axis=1)
            vb = jax.lax.dynamic_slice_in_dim(v, kj * ck, ck, axis=1)
            kpos = kj * ck + jnp.arange(ck)
            s_c, s_m, _ = _tile(qb, kb, qpos, kpos)
            p = jnp.exp(s_m - lse_b)                      # (B,nkv,g,cq,ck)
            dp = jnp.einsum("bngqh,bknh->bngqk", dob,
                            vb.astype(jnp.float32))
            dsc = p * (dp - ds_b)
            if cfg.attn_softcap is not None:
                cap = jnp.float32(cfg.attn_softcap)
                dsc = dsc * (1.0 - (s_c / cap) ** 2)
            dq_acc += jnp.einsum("bngqk,bknh->bqngh", dsc,
                                 kb.astype(jnp.float32)) * scale
            return dq_acc

        lo, hi = _kv_bounds(qi, cq, ck, nkb, tk_p, cfg)
        dq0 = jnp.zeros((b, cq, nkv, g, hd), jnp.float32)
        return jax.lax.fori_loop(lo, hi, kv_step, dq0)

    dq_blocks = jax.lax.map(per_q_block, jnp.arange(nqb))
    dq = jnp.transpose(dq_blocks, (1, 0, 2, 3, 4, 5)).reshape(
        b, tq_p, nq, hd)

    # ---------------- dK, dV ----------------
    def per_kv_block(kj):
        kb = jax.lax.dynamic_slice_in_dim(k, kj * ck, ck, axis=1)
        vb = jax.lax.dynamic_slice_in_dim(v, kj * ck, ck, axis=1)
        kpos = kj * ck + jnp.arange(ck)

        def q_step(qi, carry):
            dk_acc, dv_acc = carry
            qb = jax.lax.dynamic_index_in_dim(q5, qi, 1, keepdims=False)
            dob = jax.lax.dynamic_index_in_dim(do5, qi, 1, keepdims=False)
            dob = jnp.transpose(dob.astype(jnp.float32), (0, 2, 3, 1, 4))
            lse_b = jax.lax.dynamic_index_in_dim(
                lse5, qi, 1, keepdims=False)[..., None]
            dsb = jax.lax.dynamic_index_in_dim(dsum, qi, 1, keepdims=False)
            dsb = jnp.transpose(dsb, (0, 2, 3, 1))[..., None]
            qpos = qi * cq + jnp.arange(cq)
            s_c, s_m, _ = _tile(qb, kb, qpos, kpos)
            p = jnp.exp(s_m - lse_b)
            # dV += p^T dout   (sum over q and g)
            dv_acc += jnp.einsum("bngqk,bngqh->bknh", p, dob)
            dp = jnp.einsum("bngqh,bknh->bngqk", dob,
                            vb.astype(jnp.float32))
            dsc = p * (dp - dsb)
            if cfg.attn_softcap is not None:
                cap = jnp.float32(cfg.attn_softcap)
                dsc = dsc * (1.0 - (s_c / cap) ** 2)
            dk_acc += jnp.einsum("bngqk,bqngh->bknh", dsc,
                                 qb.astype(jnp.float32)) * scale
            return dk_acc, dv_acc

        lo, hi = _q_bounds(kj, cq, ck, nqb, cfg)
        init = (jnp.zeros((b, ck, nkv, hd), jnp.float32),
                jnp.zeros((b, ck, nkv, hd), jnp.float32))
        return jax.lax.fori_loop(lo, hi, q_step, init)

    dk_blocks, dv_blocks = jax.lax.map(per_kv_block, jnp.arange(nkb))
    dk = jnp.transpose(dk_blocks, (1, 0, 2, 3, 4)).reshape(b, tk_p, nkv, hd)
    dv = jnp.transpose(dv_blocks, (1, 0, 2, 3, 4)).reshape(b, tk_p, nkv, hd)
    return dq, dk, dv


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _flash(q, k, v, cfg: AttnConfig, kv_len: int, block: Optional[int]):
    """The jnp recurrence, or with a `block` the flash-attention
    kernels."""
    return _flash_fwd(q, k, v, cfg, kv_len, block)[0]


def _flash_fwd(q, k, v, cfg, kv_len, block):
    if block is not None:
        out, lse = flash_attn.flash_fwd(q, k, v, block)
    else:
        out, lse = _flash_fwd_impl(q, k, v, cfg, kv_len)
    return out, (q, k, v, out, lse)


def _flash_bwd(cfg, kv_len, block, res, dout):
    q, k, v, out, lse = res
    if block is not None:
        dq, dk, dv = flash_attn.flash_bwd(q, k, v, out, lse, dout, block)
    else:
        dq, dk, dv = _flash_bwd_impl(q, k, v, out, lse, dout, cfg, kv_len)
    return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype)


_flash.defvjp(_flash_fwd, _flash_bwd)


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def _kernel_block(q, k, cfg: AttnConfig, shard=None) -> Optional[int]:
    """The flash-attention kernels' block for a training call, or None
    where the call keeps the jnp loops: off a TPU, non-causal, a local
    window, a softcap, a sharded model (GSPMD cannot partition a Pallas
    call), or shapes off the kernels' tiling."""
    if (shard is not None or not _on_tpu() or not cfg.causal
            or cfg.window is not None or cfg.attn_softcap is not None):
        return None
    return flash_attn.choose_block(q.shape[1], cfg.head_dim,
                                   q.shape[2] // k.shape[2])


def blockwise_attention(
    q: jax.Array, k: jax.Array, v: jax.Array, cfg: AttnConfig,
    *, kv_len: Optional[int] = None, block: Optional[int] = None,
) -> jax.Array:
    """Online-softmax (FlashAttention-style) attention with custom VJP.

    q: (B, Tq, nq, hd); k/v: (B, Tk, nkv, hd).  Score tiles exist only one
    (chunk_q x chunk_k) block at a time, forward AND backward (the backward
    recomputes tiles, exactly like the paper's fused-loss backward).
    kv_len masks padded kv positions (defaults to Tk).

    With a `block` (from `_kernel_block`; causal self-attention whose T
    the block divides) the three `kernels/flash_attn` kernels run the
    same recurrence.  Each traced call counts its path in `repro.obs`:
    ``attn.kernel_sites`` or ``attn.jnp_sites``.
    """
    kernel_sites, jnp_sites = obs.ATTN_COUNTERS
    obs.get_registry().counter(
        jnp_sites if block is None else kernel_sites).inc()
    if block is not None:
        return _flash(q, k, v, cfg, k.shape[1], block).astype(q.dtype)
    b, tq, nq, hd = q.shape
    tk = k.shape[1]
    kv_len = tk if kv_len is None else kv_len
    cq, ck = min(cfg.chunk_q, tq), min(cfg.chunk_k, tk)
    pad_q, pad_k = (-tq) % cq, (-tk) % ck
    q = _pad_axis1(q, pad_q)
    k = _pad_axis1(k, pad_k)
    v = _pad_axis1(v, pad_k)
    out = _flash(q, k, v, cfg, kv_len, None)
    return out[:, :tq].astype(q.dtype)


def decode_attention(
    q: jax.Array, k_cache: jax.Array, v_cache: jax.Array,
    cache_len: jax.Array, cfg: AttnConfig,
) -> jax.Array:
    """Cached decode: q (B, Tq, nq, hd) vs cache (B, S, nkv, hd).

    `cache_len` (B,) is the cache length AFTER the Tq new entries were
    appended, so query i sits at absolute position ``cache_len - Tq + i``
    and attends causally to everything at or before it.  Tq == 1 is the
    classic single-step decode; Tq > 1 is the speculative-verification
    path (DESIGN.md §6.3) — scores are O(Tq * S), no tiling needed for
    the small Tq = K+1 drafts-per-step.
    """
    b, tq, nq, hd = q.shape
    s_len = k_cache.shape[1]
    nkv = k_cache.shape[2]
    g = nq // nkv
    q5 = q.reshape(b, tq, nkv, g, hd)
    s = _tile_scores(q5, k_cache, cfg)                   # (B,nkv,g,Tq,S)
    kpos = jnp.arange(s_len)
    qpos = cache_len[:, None] - tq + jnp.arange(tq)[None, :]   # (B, Tq)
    mask = kpos[None, None, :] <= qpos[:, :, None]       # (B, Tq, S)
    if cfg.window is not None:
        mask = mask & (kpos[None, None, :] > qpos[:, :, None] - cfg.window)
    s = jnp.where(mask[:, None, None, :, :], s, _NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bngqk,bknh->bqngh", p.astype(v_cache.dtype), v_cache,
                     preferred_element_type=jnp.float32)
    return out.reshape(b, tq, nq, hd).astype(q.dtype)


def extend_attention(
    q: jax.Array, k_cache: jax.Array, v_cache: jax.Array,
    cache_len: jax.Array, cfg: AttnConfig,
) -> jax.Array:
    """Suffix-prefill attention: new queries over [cached prefix ‖ fresh].

    Same signature/masking as `decode_attention`, but the arithmetic
    replicates ONE TILE of the blockwise prefill recurrence —
    ``p = exp(s - m)``, ``acc = p @ v`` (p cast to the value dtype),
    ``out = acc / max(a, 1e-30)`` — in exactly that order.  Per-row
    reductions are shape-invariant, so a prefix-cache hit's suffix rows
    come out BIT-IDENTICAL to the rows a cold single-tile blockwise
    prefill of the full prompt would have produced: shared-prefix reuse
    changes where the FLOPs come from, not a single output bit.  (For
    prompts longer than one blockwise tile — `chunk_k` — the cold path
    becomes a multi-tile online softmax and equality decays to
    numerical; serving prompts are capped at `max_len`, well under it.)

    `decode_attention` keeps the softmax-then-matmul order because the
    speculative VERIFY forward must stay bit-identical to the slab
    engine's verify, which uses it.
    """
    b, tq, nq, hd = q.shape
    s_len = k_cache.shape[1]
    nkv = k_cache.shape[2]
    g = nq // nkv
    q5 = q.reshape(b, tq, nkv, g, hd)
    s = _tile_scores(q5, k_cache, cfg)                   # (B,nkv,g,Tq,S)
    kpos = jnp.arange(s_len)
    qpos = cache_len[:, None] - tq + jnp.arange(tq)[None, :]   # (B, Tq)
    mask = kpos[None, None, :] <= qpos[:, :, None]       # (B, Tq, S)
    if cfg.window is not None:
        mask = mask & (kpos[None, None, :] > qpos[:, :, None] - cfg.window)
    s = jnp.where(mask[:, None, None, :, :], s, _NEG_INF)
    m = jnp.max(s, axis=-1)
    m_safe = jnp.where(jnp.isneginf(m), 0.0, m)
    p = jnp.exp(s - m_safe[..., None])
    a = jnp.sum(p, axis=-1)
    acc = jnp.einsum("bngqk,bknh->bngqh", p.astype(v_cache.dtype), v_cache,
                     preferred_element_type=jnp.float32)
    out = acc / jnp.maximum(a, 1e-30)[..., None]
    out = jnp.transpose(out, (0, 3, 1, 2, 4))
    return out.reshape(b, tq, nq, hd).astype(q.dtype)


# ---------------------------------------------------------------------------
# full layer: project -> attend -> output, with cache plumbing
# ---------------------------------------------------------------------------


def attention_layer(
    params, x, cfg: AttnConfig, *,
    positions: Optional[jax.Array] = None,
    cache: Optional[dict] = None,
    shard=None,
    decode: bool = False,
    prefill_ext: bool = False,
    valid: Optional[jax.Array] = None,
) -> Tuple[jax.Array, Optional[dict]]:
    """Self-attention layer.

    cache: None for training; {'k','v','len'} (dense slab), a ring
    buffer ({'pos'}), an int8 slab ({'k_scale'}) or a paged block-pool
    tree ({'kp','vp','table','len'}, DESIGN.md §8) for serving.  A paged
    tree carrying 'kp_scale'/'vp_scale' pools is QUANTIZED paging
    (DESIGN.md §10): fresh K/V quantize through `quantize_kv` before the
    block scatter and decode dequantizes per chain block — in-register
    in the pallas kernel, or through a gathered dense slab view fed to
    `_decode_quantized` on the jax oracle path.  When x
    has T > 1 and cache is given, this is a prefill (cache is filled);
    when T == 1 it is a decode step (append + attend).  ``decode=True``
    (static) forces decode semantics for T > 1 too: the new tokens are
    appended at each row's own cache position and attend over the FULL
    cache with per-row absolute-position causal masking — the
    speculative-verification path.  ``prefill_ext=True`` (static, with
    ``decode=True``) marks the extension as a paged SUFFIX PREFILL after
    a prefix-cache hit: the math switches to `extend_attention`, whose
    per-row arithmetic is bit-identical to the cold blockwise prefill —
    reusing a cached prefix must not change one output bit.
    ``valid`` (B, T) marks real (non-bucket-pad) positions of a padded
    prefill: slab/paged writes are position-addressed and self-heal, but
    ring-buffer writes must tag pad entries dead (see `_ring_update`).
    Returns (out, new_cache).
    """
    b, t, _ = x.shape
    if positions is None:
        if cache is not None:
            positions = cache["len"][:, None] + jnp.arange(t)[None, :]
        else:
            positions = jnp.broadcast_to(jnp.arange(t)[None, :], (b, t))
    q, k, v = _project_qkv(params, x, positions, cfg)
    # no explicit q/k/v constraints: GSPMD propagates the (repaired)
    # weight shardings; mixed explicit specs here caused involuntary
    # resharding/remat inside the flash loops (see EXPERIMENTS §Perf).

    is_decode = decode or t == 1
    new_cache = None
    if cache is None:
        out = blockwise_attention(q, k, v, cfg,
                                  block=_kernel_block(q, k, cfg, shard))
    elif "table" in cache:                                # paged block-pool
        quant = "kp_scale" in cache             # int8 pools + scale pools
        table = cache["table"]
        if quant:
            kq, ks = quantize_kv(k)
            vq, vs = quantize_kv(v)
            kp = _paged_update(cache["kp"], table, kq, cache["len"])
            vp = _paged_update(cache["vp"], table, vq, cache["len"])
            kps = _paged_update(cache["kp_scale"], table, ks, cache["len"])
            vps = _paged_update(cache["vp_scale"], table, vs, cache["len"])
        else:
            kp = _paged_update(cache["kp"], table, k, cache["len"])
            vp = _paged_update(cache["vp"], table, v, cache["len"])
        new_len = cache["len"] + t
        new_cache = {"kp": kp, "vp": vp, "table": table, "len": new_len}
        if quant:
            new_cache["kp_scale"] = kps
            new_cache["vp_scale"] = vps
        if is_decode:
            if cfg.window is not None:
                raise NotImplementedError(
                    "paged decode has no local-window path (windowed "
                    "caches are ring buffers, already O(window))")
            if prefill_ext:
                if quant:
                    # suffix prefill over a dequantized chain view; the
                    # scale factors mirror `_decode_quantized` (K back
                    # to the query dtype, V in f32)
                    kd = (gather_paged_kv(kp, table).astype(jnp.float32)
                          * gather_paged_kv(kps, table)).astype(q.dtype)
                    vd = (gather_paged_kv(vp, table).astype(jnp.float32)
                          * gather_paged_kv(vps, table))
                    out = extend_attention(q, kd, vd, new_len, cfg)
                else:
                    out = extend_attention(q, gather_paged_kv(kp, table),
                                           gather_paged_kv(vp, table),
                                           new_len, cfg)
            elif cfg.paged_impl == "pallas":
                from repro.kernels.paged_attn import pallas_paged_attention
                out = pallas_paged_attention(
                    q, kp, vp, table, new_len,
                    kp_scale=kps if quant else None,
                    vp_scale=vps if quant else None,
                    softcap=cfg.attn_softcap, pages_per_step=1)
            elif quant:
                # pure-jnp oracle: gather the chains into a dense
                # quantized-slab view and reuse the slab decode math
                dense = {"k": gather_paged_kv(kp, table),
                         "v": gather_paged_kv(vp, table),
                         "k_scale": gather_paged_kv(kps, table),
                         "v_scale": gather_paged_kv(vps, table),
                         "len": new_len}
                out = _decode_quantized(q, dense, cfg)
            else:
                out = decode_attention(q, gather_paged_kv(kp, table),
                                       gather_paged_kv(vp, table),
                                       new_len, cfg)
        else:
            # cold prefill: the chain is empty, attend within the fresh
            # segment (same as the slab prefill path)
            out = blockwise_attention(q, k, v, cfg)
    elif "pos" in cache:                                  # ring-buffer local
        new_cache = _ring_update(cache, k, v, valid=valid)
        if is_decode:
            out = _ring_decode(q, new_cache, cfg)
        else:
            out = blockwise_attention(q, k, v, cfg)
    elif "k_scale" in cache:                              # int8 quantized
        kq, ks = quantize_kv(k)
        vq, vs = quantize_kv(v)
        new_cache = {
            "k": _update_cache(cache["k"], kq, cache["len"]),
            "v": _update_cache(cache["v"], vq, cache["len"]),
            "k_scale": _update_cache(cache["k_scale"], ks, cache["len"]),
            "v_scale": _update_cache(cache["v_scale"], vs, cache["len"]),
            "len": cache["len"] + t,
        }
        if is_decode:
            out = _decode_quantized(q, new_cache, cfg)
        else:
            out = blockwise_attention(q, k, v, cfg)       # fresh prefill
    else:
        k_cache = _update_cache(cache["k"], k, cache["len"])
        v_cache = _update_cache(cache["v"], v, cache["len"])
        new_len = cache["len"] + t
        new_cache = {"k": k_cache, "v": v_cache, "len": new_len}
        if is_decode:
            out = decode_attention(q, k_cache, v_cache, new_len, cfg)
        else:
            # prefill: attend within the fresh segment (cache assumed empty
            # before prefill; positions start at cache['len'])
            out = blockwise_attention(q, k, v, cfg)
    y = jnp.einsum("btnh,nhd->btd", out.astype(x.dtype), params["wo"])
    if shard is not None:
        y = shard(y, "batch", "seq", "embed")
    return y, new_cache


def _update_cache(cache_arr, new_vals, cur_len):
    """Write new_vals at position cur_len along the time axis (per batch).

    With a per-row `cur_len` (batched serving) each row scatters at its
    OWN length — under continuous batching the slots of a batch sit at
    different positions; t > 1 writes a contiguous per-row slab (the
    speculative-verification append).  Entries that would run past the
    cache are clamped into the last slot — callers guarantee capacity
    for live rows, so only dead/ghost rows ever clamp.
    A scalar `cur_len` writes one uniform slab (batch == 1 prefill).
    """
    b, t = new_vals.shape[:2]
    if jnp.ndim(cur_len) == 0:
        return jax.lax.dynamic_update_slice_in_dim(
            cache_arr, new_vals.astype(cache_arr.dtype), cur_len, axis=1)
    if b == 1:
        # one row: a contiguous dynamic-update-slice beats a scatter —
        # this is the slot engine's per-request prefill hot path
        return jax.lax.dynamic_update_slice_in_dim(
            cache_arr, new_vals.astype(cache_arr.dtype), cur_len[0],
            axis=1)
    idx = jnp.clip(cur_len[:, None] + jnp.arange(t)[None, :],
                   0, cache_arr.shape[1] - 1)            # (B, t)
    return cache_arr.at[jnp.arange(b)[:, None], idx].set(
        new_vals.astype(cache_arr.dtype))


def _paged_update(pool, table, new_vals, cur_len):
    """Scatter new_vals (B, t, nkv, hd) into the shared block pool.

    Position ``p`` of row ``b`` lives in pool block ``table[b, p // bs]``
    at slot ``p % bs``; rows write disjoint blocks by construction (the
    host allocator hands each chain its own blocks), so the scatter is
    conflict-free.  Rows whose chain is exhausted (ghost slots running
    past capacity, or free slots whose table is null-filled) clamp into
    the reserved null block 0 — never read (masked by ``len``).
    """
    b, t = new_vals.shape[:2]
    n, bs = pool.shape[:2]
    pos = cur_len[:, None] + jnp.arange(t)[None, :]          # (B, t)
    col = jnp.clip(pos // bs, 0, table.shape[1] - 1)
    blk = jnp.take_along_axis(table, col, axis=1)            # (B, t)
    slot = blk * bs + pos % bs                               # flat pool slot
    flat = pool.reshape((n * bs,) + pool.shape[2:])
    flat = flat.at[slot].set(new_vals.astype(pool.dtype))
    return flat.reshape(pool.shape)


def gather_paged_kv(pool, table):
    """(N, bs, nkv, hd) x (B, nb) -> (B, nb*bs, nkv, hd): materialize a
    row-major view of each row's block chain (entry ``p`` is absolute
    position ``p``).  The pure-jnp oracle path of the paged decode —
    `kernels/paged_attn` computes the same attention without it."""
    b, nb = table.shape
    bs = pool.shape[1]
    g = pool[table]                                  # (B, nb, bs, nkv, hd)
    return g.reshape(b, nb * bs, *pool.shape[2:])


def init_cache(batch, max_len, cfg: AttnConfig, dtype=jnp.bfloat16,
               quantize: bool = False):
    """KV cache; quantize=True stores int8 K/V with per-(token, head)
    f32 scales — 2x less HBM per cached token, dequantized chunk-wise
    during decode (see `_decode_quantized`)."""
    shape = (batch, max_len, cfg.num_kv_heads, cfg.head_dim)
    if quantize:
        sshape = shape[:-1] + (1,)
        return {
            "k": jnp.zeros(shape, jnp.int8),
            "v": jnp.zeros(shape, jnp.int8),
            "k_scale": jnp.zeros(sshape, jnp.float32),
            "v_scale": jnp.zeros(sshape, jnp.float32),
            "len": jnp.zeros((batch,), jnp.int32),
        }
    return {
        "k": jnp.zeros(shape, dtype),
        "v": jnp.zeros(shape, dtype),
        "len": jnp.zeros((batch,), jnp.int32),
    }


def quantize_kv(x):
    """(…, hd) -> (int8 values, f32 scale broadcast over hd)."""
    s = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=-1, keepdims=True)
    s = jnp.maximum(s / 127.0, 1e-8)
    q = jnp.clip(jnp.round(x.astype(jnp.float32) / s), -127, 127)
    return q.astype(jnp.int8), s


def _decode_quantized(q, cache, cfg: AttnConfig, chunk: int = 4096):
    """Decode against an int8 cache, dequantizing one chunk at a time
    (bounded transient memory; online-softmax merge across chunks).
    Tq >= 1 queries: query i is at absolute position ``len - Tq + i``
    and attends causally (the speculative-verification path)."""
    b, tq, nq, hd = q.shape
    s_len = cache["k"].shape[1]
    nkv = cache["k"].shape[2]
    g = nq // nkv
    ck = min(chunk, s_len)
    pad = (-s_len) % ck
    nkb = (s_len + pad) // ck
    q5 = q.reshape(b, tq, nkv, g, hd)
    cache_len = cache["len"] + 0
    qpos = cache_len[:, None] - tq + jnp.arange(tq)[None, :]   # (B, Tq)

    def step(kj, carry):
        m, a, acc = carry
        # clamp the start for the ragged tail; overlapped positions are
        # excluded by the chunk-ownership mask below (never double-counted)
        start = jnp.minimum(kj * ck, s_len - ck)
        kq = jax.lax.dynamic_slice_in_dim(cache["k"], start, ck, axis=1)
        vq = jax.lax.dynamic_slice_in_dim(cache["v"], start, ck, axis=1)
        ks = jax.lax.dynamic_slice_in_dim(cache["k_scale"], start, ck,
                                          axis=1)
        vs = jax.lax.dynamic_slice_in_dim(cache["v_scale"], start, ck,
                                          axis=1)
        kb = kq.astype(jnp.float32) * ks
        vb = vq.astype(jnp.float32) * vs
        s = _tile_scores(q5, kb.astype(q.dtype), cfg)    # (B,nkv,g,Tq,ck)
        kpos = start + jnp.arange(ck)
        own = (kpos >= kj * ck) & (kpos < (kj + 1) * ck)
        mask = own[None, None, :] & \
            (kpos[None, None, :] <= qpos[:, :, None])    # (B, Tq, ck)
        if cfg.window is not None:
            mask = mask & (kpos[None, None, :] > qpos[:, :, None]
                           - cfg.window)
        s = jnp.where(mask[:, None, None, :, :], s, _NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        m_safe = jnp.where(jnp.isneginf(m_new), 0.0, m_new)
        p = jnp.exp(s - m_safe[..., None])
        scale_prev = jnp.exp(m - m_safe)
        a = a * scale_prev + jnp.sum(p, axis=-1)
        pv = jnp.einsum("bngqk,bknh->bngqh", p, vb,
                        preferred_element_type=jnp.float32)
        acc = acc * scale_prev[..., None] + pv
        return m_new, a, acc

    init = (jnp.full((b, nkv, g, tq), _NEG_INF, jnp.float32),
            jnp.zeros((b, nkv, g, tq), jnp.float32),
            jnp.zeros((b, nkv, g, tq, hd), jnp.float32))
    m, a, acc = jax.lax.fori_loop(0, nkb, step, init)
    out = acc / jnp.maximum(a, 1e-30)[..., None]
    out = jnp.transpose(out, (0, 3, 1, 2, 4)).reshape(b, tq, nq, hd)
    return out.astype(q.dtype)


# ---------------------------------------------------------------------------
# ring-buffer cache for local-window attention (O(window) memory at any T —
# this is what makes recurrentgemma's 524k-token decode cache 2048 entries)
# ---------------------------------------------------------------------------


def init_local_cache(batch, window, cfg: AttnConfig, dtype=jnp.bfloat16):
    shape = (batch, window, cfg.num_kv_heads, cfg.head_dim)
    return {
        "k": jnp.zeros(shape, dtype),
        "v": jnp.zeros(shape, dtype),
        "pos": jnp.full((batch, window), -1, jnp.int32),  # absolute positions
        "len": jnp.zeros((batch,), jnp.int32),
    }


def _ring_update(cache, k, v, valid=None):
    """Append T new kv entries at slots (len + i) % window.

    ``valid`` (B, T) marks the real positions of a bucket-padded prefill:
    pad entries still occupy their ring slot (the slot index must follow
    the absolute position so later decode writes land on them) but their
    stored ``pos`` is -1 — `_ring_decode` masks them exactly, so a
    padded prefill leaves the attention-visible state identical to an
    exact-length one.  Callers must not let pad positions WRAP the ring
    (engine-side bucket cap: bucket <= window), since a wrapped write
    overwrites an in-window real entry that cannot be restored."""
    b, t = k.shape[:2]
    window = cache["k"].shape[1]
    pos_new = cache["len"][:, None] + jnp.arange(t)[None, :]  # absolute
    slots = pos_new % window                                   # (B, T)
    bidx = jnp.arange(b)[:, None]
    store_pos = pos_new if valid is None else \
        jnp.where(valid, pos_new, -1)
    k_c = cache["k"].at[bidx, slots].set(k.astype(cache["k"].dtype))
    v_c = cache["v"].at[bidx, slots].set(v.astype(cache["v"].dtype))
    p_c = cache["pos"].at[bidx, slots].set(store_pos)
    return {"k": k_c, "v": v_c, "pos": p_c, "len": cache["len"] + t}


def _ring_decode(q, cache, cfg: AttnConfig):
    """Decode against the ring buffer using stored absolute positions.

    Handles Tq >= 1 new queries: query i sits at absolute position
    ``len - Tq + i`` (`len` counts the Tq entries just ring-appended)
    and attends to every in-window cache entry at or before it."""
    b, tq, nq, hd = q.shape
    nkv = cache["k"].shape[2]
    g = nq // nkv
    q5 = q.reshape(b, tq, nkv, g, hd)
    s = _tile_scores(q5, cache["k"], cfg)                 # (B,nkv,g,Tq,W)
    qpos = cache["len"][:, None] - tq + jnp.arange(tq)[None, :]  # (B, Tq)
    kpos = cache["pos"]                                   # (B, W)
    mask = (kpos[:, None, :] >= 0) & (kpos[:, None, :] <= qpos[:, :, None])
    if cfg.window is not None:
        mask = mask & (kpos[:, None, :] > qpos[:, :, None] - cfg.window)
    s = jnp.where(mask[:, None, None, :, :], s, _NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bngqk,bknh->bqngh", p.astype(cache["v"].dtype),
                     cache["v"], preferred_element_type=jnp.float32)
    return out.reshape(b, tq, nq, hd).astype(q.dtype)
