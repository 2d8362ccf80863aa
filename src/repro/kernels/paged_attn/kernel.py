"""Pallas TPU kernel: paged decode attention over a block-pool KV cache.

The serving KV cache is a pool of fixed-size token blocks
(``kp/vp: (n_blocks, block_size, n_kv, head_dim)``) and each batch row
owns an ordered *chain* of pool blocks through its block-table row
(``table[b, j]`` holds positions ``j*block_size .. (j+1)*block_size-1``
of row ``b`` — DESIGN.md §8).  Decode attention must therefore gather
scattered pool blocks; materializing the gathered ``(B, S, n_kv, hd)``
cache in HBM would re-create exactly the dense slab paging removed.

This kernel never materializes the gather.  The block table and the
per-row cache lengths ride in as **scalar-prefetch** operands
(`pltpu.PrefetchScalarGridSpec`), so the BlockSpec index maps themselves
chase the chain: grid step ``(b, j)`` DMAs pool blocks
``table[b, j*ppb .. j*ppb+ppb-1]`` straight into VMEM — data-dependent
block fetches, the TPU analogue of the CUDA paged-attention gather.

Everything else is this repo's standard online-softmax layout
(DESIGN.md §2): rows parallel, the chain axis innermost and sequential,
``(m, a, acc)`` carried in VMEM scratch across chain steps, epilogue
write on the last step.  Scores follow `models/attention._tile_scores`
exactly (1/sqrt(hd) scale, optional tanh softcap, f32 accumulation), and
masking is per-row absolute-position causal: query ``i`` of ``Tq`` sits
at ``lens[b] - Tq + i`` (``Tq > 1`` is the speculative-verification
path).  Ghost rows (``lens == 0``) mask everything and emit zeros.

``pages_per_step`` (ppb) is how many pool blocks one sequential grid
step fetches (more DMAs in flight per step).  The serving path runs one
page a step (DESIGN.md §3.2); the argument stays for callers and tests
that sweep it.

`models/attention.py`'s gather-based `decode_attention` path is the
pure-jnp oracle (`tests/test_paged_attn.py` holds the equivalence).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.pallas_utils import compiler_params, interpret_default

_NEG_INF = float("-inf")
_LANE = 128
_SUBLANE = 8


def _round_up(x: int, m: int) -> int:
    return -(-int(x) // m) * m


def _paged_kernel(tab_ref, len_ref,                 # scalar prefetch
                  q_ref, *refs,
                  ppb: int, bs: int, tq: int, nkv: int, g: int, hd: int,
                  n_steps: int, scale: float, softcap: Optional[float],
                  quantized: bool = False):
    """refs layout: ppb k-page refs, ppb v-page refs, [2*ppb scale-page
    refs when quantized,] out ref, then the (m, a, acc) VMEM scratch.
    Scratch rows are grouped per kv head: rows ``n*g*tq .. (n+1)*g*tq``
    belong to head ``n``.

    Quantized pages hold int8 K/V with per-(token, head) f32 scales
    (`models/attention.quantize_kv`); each page's K tile is dequantized
    in-register — cast + one multiply per kv head — and cast back to the
    query dtype before the score dot, matching `_decode_quantized`'s
    slab math bit-for-bit.  V dequantizes to f32 for the pv accumulate.
    The full dequantized cache never exists anywhere (DESIGN.md §10.1).
    """
    k_refs = refs[:ppb]
    v_refs = refs[ppb:2 * ppb]
    if quantized:
        ks_refs = refs[2 * ppb:3 * ppb]
        vs_refs = refs[3 * ppb:4 * ppb]
        o_ref = refs[4 * ppb]
        m_sc, a_sc, acc_sc = refs[4 * ppb + 1:]
    else:
        ks_refs = vs_refs = None
        o_ref = refs[2 * ppb]
        m_sc, a_sc, acc_sc = refs[2 * ppb + 1:]

    b = pl.program_id(0)
    j = pl.program_id(1)
    gtq = g * tq

    @pl.when(j == 0)
    def _init():
        m_sc[...] = jnp.full_like(m_sc[...], _NEG_INF)
        a_sc[...] = jnp.zeros_like(a_sc[...])
        acc_sc[...] = jnp.zeros_like(acc_sc[...])

    cache_len = len_ref[b]

    for i in range(ppb):
        col = j * ppb + i                        # RAW chain column: pages
        kb = k_refs[i][0]                        # past the clamp mask out
        vb = v_refs[i][0]                        # (bs, nkv*hd)
        ksb = ks_refs[i][0] if quantized else None   # (bs, nkv) f32
        vsb = vs_refs[i][0] if quantized else None
        for n in range(nkv):
            sl = slice(n * gtq, (n + 1) * gtq)
            q_n = q_ref[0, sl, :]                            # (gtq, hd)
            k_n = kb[:, n * hd:(n + 1) * hd]                 # (bs, hd)
            v_n = vb[:, n * hd:(n + 1) * hd]
            if quantized:
                # per-token dequant, one page tile at a time; K back to
                # the query dtype so the MXU dot matches the slab oracle
                k_n = (k_n.astype(jnp.float32)
                       * ksb[:, n:n + 1]).astype(q_n.dtype)
                v_n = v_n.astype(jnp.float32) * vsb[:, n:n + 1]
            s = jax.lax.dot_general(
                q_n, k_n, dimension_numbers=(((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * scale  # (gtq, bs)
            if softcap is not None:
                cap = jnp.float32(softcap)
                s = cap * jnp.tanh(s / cap)
            kpos = col * bs + jax.lax.broadcasted_iota(
                jnp.int32, (gtq, bs), 1)
            ti = jax.lax.broadcasted_iota(jnp.int32, (gtq, bs), 0) % tq
            qpos = cache_len - tq + ti
            s = jnp.where(kpos <= qpos, s, _NEG_INF)

            m_prev = m_sc[sl, :]                             # (gtq, LANE)
            a_prev = a_sc[sl, :]
            s_max = jnp.max(s, axis=1, keepdims=True)        # (gtq, 1)
            m_new = jnp.maximum(m_prev, s_max)
            m_safe = jnp.where(jnp.isneginf(m_new), 0.0, m_new)
            p = jnp.exp(s - m_safe[:, :1])                   # (gtq, bs)
            scale_prev = jnp.exp(m_prev - m_safe)            # (gtq, LANE)
            a_new = a_prev * scale_prev + jnp.sum(p, axis=1,
                                                  keepdims=True)
            pv = jax.lax.dot_general(
                p, v_n.astype(jnp.float32),
                dimension_numbers=(((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)          # (gtq, hd)
            m_sc[sl, :] = m_new
            a_sc[sl, :] = a_new
            acc_sc[sl, :] = acc_sc[sl, :] * scale_prev[:, :1] + pv

    @pl.when(j == n_steps - 1)
    def _epilogue():
        a_fin = jnp.maximum(a_sc[:, :1], 1e-30)
        o_ref[0] = acc_sc[...] / a_fin


def pallas_paged_attention(
    q: jax.Array, kp: jax.Array, vp: jax.Array,
    table: jax.Array, lens: jax.Array, *,
    kp_scale: Optional[jax.Array] = None,
    vp_scale: Optional[jax.Array] = None,
    softcap: Optional[float] = None,
    pages_per_step: int = 1,
    interpret: Optional[bool] = None,
) -> jax.Array:
    """Paged decode attention without materializing the gathered cache.

    q: (B, Tq, nq, hd); kp/vp: (N, bs, nkv, hd); table: (B, nb) int32
    block-chain rows (null block 0 beyond each chain); lens: (B,) cache
    length AFTER the Tq entries were appended.  Returns (B, Tq, nq, hd)
    in q's dtype; rows with ``lens == 0`` (ghost slots) return zeros.

    `kp_scale`/`vp_scale` ((N, bs, nkv, 1) f32) mark the pools as
    int8-quantized (`quantize_kv` layout): scale pages DMA alongside the
    value pages through the same table-chasing index maps and each K/V
    tile dequantizes in-register under the online-softmax scan — neither
    the dense gathered cache NOR a dequantized pool ever exists.
    """
    b, tq, nq, hd = q.shape
    n_pool, bs, nkv = kp.shape[0], kp.shape[1], kp.shape[2]
    nb = table.shape[1]
    g = nq // nkv
    gtq = g * tq
    rows = nkv * gtq
    rows_pad = _round_up(rows, _SUBLANE)
    ppb = max(1, min(pages_per_step, nb))
    n_steps = -(-nb // ppb)
    scale = 1.0 / np.sqrt(hd)
    interpret = interpret_default() if interpret is None else interpret
    quantized = kp_scale is not None
    if quantized and vp_scale is None:
        raise ValueError("kp_scale given without vp_scale")

    # rows grouped per kv head: row (n*g + gi)*tq + ti
    q_r = q.reshape(b, tq, nkv, g, hd)
    q_r = jnp.transpose(q_r, (0, 2, 3, 1, 4)).reshape(b, rows, hd)
    if rows_pad != rows:
        q_r = jnp.pad(q_r, ((0, 0), (0, rows_pad - rows), (0, 0)))
    kp_f = kp.reshape(n_pool, bs, nkv * hd)
    vp_f = vp.reshape(n_pool, bs, nkv * hd)

    def page_spec(i, width):
        def index(bi, ji, tab_ref, len_ref):
            del len_ref
            col = jnp.minimum(ji * ppb + i, nb - 1)
            return (tab_ref[bi, col], 0, 0)
        return pl.BlockSpec((1, bs, width), index)

    in_specs = ([page_spec(i, nkv * hd) for i in range(ppb)] * 2)
    inputs = [*([kp_f] * ppb), *([vp_f] * ppb)]
    if quantized:
        ks_f = kp_scale.astype(jnp.float32).reshape(n_pool, bs, nkv)
        vs_f = vp_scale.astype(jnp.float32).reshape(n_pool, bs, nkv)
        in_specs += [page_spec(i, nkv) for i in range(ppb)] * 2
        inputs += [*([ks_f] * ppb), *([vs_f] * ppb)]

    row_spec = pl.BlockSpec((1, rows_pad, hd),
                            lambda bi, ji, tab_ref, len_ref: (bi, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, n_steps),
        in_specs=[row_spec] + in_specs,
        out_specs=row_spec,
        scratch_shapes=[pltpu.VMEM((rows_pad, _LANE), jnp.float32),
                        pltpu.VMEM((rows_pad, _LANE), jnp.float32),
                        pltpu.VMEM((rows_pad, hd), jnp.float32)],
    )
    kern = functools.partial(
        _paged_kernel, ppb=ppb, bs=bs, tq=tq, nkv=nkv, g=g, hd=hd,
        n_steps=n_steps, scale=scale, softcap=softcap,
        quantized=quantized)
    out = pl.pallas_call(
        kern,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, rows_pad, hd), jnp.float32),
        compiler_params=compiler_params(),
        interpret=interpret,
        name="paged_attn",
    )(table.astype(jnp.int32), lens.astype(jnp.int32), q_r, *inputs)
    out = out[:, :rows].reshape(b, nkv, g, tq, hd)
    return jnp.transpose(out, (0, 3, 1, 2, 4)).reshape(
        b, tq, nq, hd).astype(q.dtype)
