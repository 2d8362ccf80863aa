"""Paged decode-attention kernel vs the gather-based jnp oracle.

The oracle is `attention.decode_attention` over `gather_paged_kv` — the
exact math the slab engine runs, so kernel-vs-oracle equivalence plus
the paged-engine token-identity tests (tests/test_paged_engine.py) pin
the whole paged decode path."""

import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.paged_attn import pallas_paged_attention
from repro.models.attention import (AttnConfig, decode_attention,
                                    gather_paged_kv, _paged_update)


def _case(rng, b, tq, nq, nkv, hd, bs, nb, dtype=jnp.float32):
    n_pool = b * nb + 1
    kp = jnp.asarray(rng.standard_normal((n_pool, bs, nkv, hd)), dtype)
    vp = jnp.asarray(rng.standard_normal((n_pool, bs, nkv, hd)), dtype)
    perm = rng.permutation(n_pool - 1)[:b * nb] + 1    # disjoint chains
    table = jnp.asarray(perm.reshape(b, nb), jnp.int32)
    lens = jnp.asarray(rng.integers(tq, nb * bs + 1, (b,)), jnp.int32)
    q = jnp.asarray(rng.standard_normal((b, tq, nq, hd)), dtype)
    return q, kp, vp, table, lens


@pytest.mark.parametrize("b,tq,nq,nkv,hd,bs,nb,ppb,cap", [
    (3, 1, 4, 2, 16, 4, 6, 1, None),       # GQA single-token decode
    (2, 3, 4, 1, 8, 8, 4, 2, 30.0),        # spec verify (Tq>1) + softcap
    (1, 1, 2, 2, 32, 16, 3, 3, None),      # ppb > 1 with ragged last step
    (4, 2, 6, 3, 8, 4, 5, 4, None),        # ppb not dividing nb
])
def test_kernel_matches_gather_oracle(b, tq, nq, nkv, hd, bs, nb, ppb, cap):
    rng = np.random.default_rng(b * 100 + tq)
    q, kp, vp, table, lens = _case(rng, b, tq, nq, nkv, hd, bs, nb)
    cfg = AttnConfig(d_model=nq * hd, num_heads=nq, num_kv_heads=nkv,
                     head_dim=hd, attn_softcap=cap)
    ref = decode_attention(q, gather_paged_kv(kp, table),
                           gather_paged_kv(vp, table), lens, cfg)
    out = pallas_paged_attention(q, kp, vp, table, lens, softcap=cap,
                                 pages_per_step=ppb)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


def test_kernel_bf16_inputs():
    rng = np.random.default_rng(7)
    q, kp, vp, table, lens = _case(rng, 2, 1, 4, 2, 16, 4, 4,
                                   dtype=jnp.bfloat16)
    cfg = AttnConfig(d_model=64, num_heads=4, num_kv_heads=2, head_dim=16)
    ref = decode_attention(q, gather_paged_kv(kp, table),
                           gather_paged_kv(vp, table), lens, cfg)
    out = pallas_paged_attention(q, kp, vp, table, lens)
    assert out.dtype == jnp.bfloat16
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               atol=3e-2, rtol=3e-2)


def test_ghost_rows_emit_zeros():
    rng = np.random.default_rng(3)
    q, kp, vp, table, _ = _case(rng, 2, 1, 4, 2, 16, 4, 4)
    lens = jnp.zeros((2,), jnp.int32)
    out = pallas_paged_attention(q, kp, vp, table, lens)
    assert float(jnp.max(jnp.abs(out))) == 0.0


def test_null_tail_blocks_are_masked():
    """Chain columns past a row's length point at the null block; its
    (garbage) content must not leak into the output."""
    rng = np.random.default_rng(5)
    q, kp, vp, table, _ = _case(rng, 1, 1, 2, 1, 8, 4, 4)
    kp = kp.at[0].set(1e9)                 # poison the null block
    vp = vp.at[0].set(1e9)
    table = table.at[0, 2:].set(0)         # chain of 2 real blocks
    lens = jnp.asarray([7], jnp.int32)
    cfg = AttnConfig(d_model=16, num_heads=2, num_kv_heads=1, head_dim=8)
    ref = decode_attention(q, gather_paged_kv(kp, table)[:, :8],
                           gather_paged_kv(vp, table)[:, :8], lens, cfg)
    out = pallas_paged_attention(q, kp, vp, table, lens)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


def test_paged_update_scatters_into_chain_blocks():
    pool = jnp.zeros((5, 4, 2, 8))
    table = jnp.asarray([[2, 3, 0], [4, 0, 0]], jnp.int32)
    new = jnp.ones((2, 2, 2, 8))
    # row 0 appends at positions 3,4 (spans blocks 2 -> 3); row 1 at 0,1
    out = _paged_update(pool, table, new, jnp.asarray([3, 0], jnp.int32))
    assert float(out[2, 3].sum()) == 2 * 8       # pos 3 -> block 2 slot 3
    assert float(out[3, 0].sum()) == 2 * 8       # pos 4 -> block 3 slot 0
    assert float(out[4, 0].sum()) == 2 * 8
    assert float(out[4, 1].sum()) == 2 * 8
    assert float(out[1].sum()) == 0.0            # untouched block
    # ghost rows past capacity clamp into their table's last column
    ghost = _paged_update(pool, jnp.zeros((1, 3), jnp.int32),
                          jnp.ones((1, 1, 2, 8)),
                          jnp.asarray([50], jnp.int32))
    assert float(ghost[1:].sum()) == 0.0         # only null block written


def test_paged_update_matches_slab_update_content():
    """Paged writes then gather == slab dynamic-update at equal length."""
    rng = np.random.default_rng(11)
    b, t, nkv, hd, bs, nb = 2, 3, 2, 8, 4, 4
    slab = jnp.zeros((b, nb * bs, nkv, hd))
    pool = jnp.zeros((b * nb + 1, bs, nkv, hd))
    table = jnp.asarray(1 + np.arange(b * nb).reshape(b, nb), jnp.int32)
    new = jnp.asarray(rng.standard_normal((b, t, nkv, hd)), jnp.float32)
    lens = jnp.asarray([5, 0], jnp.int32)
    from repro.models.attention import _update_cache
    ref = _update_cache(slab, new, lens)
    out = gather_paged_kv(_paged_update(pool, table, new, lens), table)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))


# ---------------------------------------------------------------------------
# quantized pools: in-register dequant vs the slab _decode_quantized oracle
# ---------------------------------------------------------------------------


def _quant_case(rng, b, tq, nq, nkv, hd, bs, nb):
    """Quantized pools built by scattering quantize_kv slabs block-wise,
    so the pool content is bit-identical to a quantized slab cache."""
    from repro.models.attention import quantize_kv
    n_pool = b * nb + 1
    s = nb * bs
    k = jnp.asarray(rng.standard_normal((b, s, nkv, hd)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((b, s, nkv, hd)), jnp.float32)
    kq, ks = quantize_kv(k)
    vq, vs = quantize_kv(v)
    perm = rng.permutation(n_pool - 1)[:b * nb] + 1
    table = jnp.asarray(perm.reshape(b, nb), jnp.int32)
    pools = [jnp.zeros((n_pool, bs, nkv, hd), jnp.int8),
             jnp.zeros((n_pool, bs, nkv, hd), jnp.int8),
             jnp.zeros((n_pool, bs, nkv, 1), jnp.float32),
             jnp.zeros((n_pool, bs, nkv, 1), jnp.float32)]
    for bi in range(b):
        for j in range(nb):
            pb = int(table[bi, j])
            for pi, slab in enumerate((kq, vq, ks, vs)):
                pools[pi] = pools[pi].at[pb].set(
                    slab[bi, j * bs:(j + 1) * bs])
    lens = jnp.asarray(rng.integers(tq, s + 1, (b,)), jnp.int32)
    q = jnp.asarray(rng.standard_normal((b, tq, nq, hd)), jnp.bfloat16)
    dense = {"k": kq, "v": vq, "k_scale": ks, "v_scale": vs, "len": lens}
    return q, pools, table, lens, dense


@pytest.mark.parametrize("b,tq,nq,nkv,hd,bs,nb,ppb,cap", [
    (3, 1, 4, 2, 16, 4, 6, 1, None),       # GQA single-token decode
    (2, 3, 4, 1, 8, 8, 4, 2, 30.0),        # spec verify (Tq>1) + softcap
    (1, 1, 2, 2, 32, 16, 3, 3, None),      # ppb > 1 with ragged last step
])
def test_quantized_kernel_matches_slab_decode(b, tq, nq, nkv, hd, bs, nb,
                                              ppb, cap):
    """Bit-for-bit against `_decode_quantized` on the dense slab view:
    at nb*bs <= the oracle's chunk the slab decode is a single online-
    softmax chunk, the same math the kernel runs per page."""
    from repro.models.attention import _decode_quantized
    rng = np.random.default_rng(b * 10 + tq)
    q, (kp, vp, kps, vps), table, lens, dense = _quant_case(
        rng, b, tq, nq, nkv, hd, bs, nb)
    cfg = AttnConfig(d_model=nq * hd, num_heads=nq, num_kv_heads=nkv,
                     head_dim=hd, attn_softcap=cap)
    ref = _decode_quantized(q, dense, cfg)
    out = pallas_paged_attention(q, kp, vp, table, lens,
                                 kp_scale=kps, vp_scale=vps,
                                 softcap=cap, pages_per_step=ppb)
    np.testing.assert_array_equal(np.asarray(out, np.float32),
                                  np.asarray(ref, np.float32))


def test_quantized_kernel_requires_both_scales():
    rng = np.random.default_rng(0)
    q, (kp, vp, kps, _), table, lens, _ = _quant_case(
        rng, 1, 1, 2, 1, 8, 4, 2)
    with pytest.raises(ValueError, match="vp_scale"):
        pallas_paged_attention(q, kp, vp, table, lens, kp_scale=kps)
