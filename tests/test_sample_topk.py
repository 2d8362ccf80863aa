"""Streaming top-k Pallas kernel: oracle equivalence + plan machinery.

The dense oracle is a stable `jnp.argsort` over the masked (softcapped)
logits; the kernel contract is BIT-identical output including tie order
(lowest index first).  The pure-JAX `streaming_topk` is held to the same
contract so either can stand in for the other.

A deterministic parameter grid always runs (ties, `valid_vocab` masking,
softcap, k >= valid, k > V, b < sublane); a hypothesis fuzz over the
same space runs additionally when the 'test' extra is installed.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.windows import BlockPlan, tile_bytes
from repro.kernels.sample_topk import pallas_topk
from repro.serve.sampler import streaming_topk

try:
    from hypothesis import given, settings, strategies as st
    _HAVE_HYPOTHESIS = True
except ImportError:                     # pragma: no cover - 'test' extra
    _HAVE_HYPOTHESIS = False


def _dense_oracle(h, w, k, valid, cap):
    z = h.astype(jnp.float32) @ w.T.astype(jnp.float32)
    if cap is not None:
        z = cap * jnp.tanh(z / cap)
    v = w.shape[0]
    z = jnp.where(jnp.arange(v)[None, :] < valid, z, -jnp.inf)
    order = jnp.argsort(-z, axis=-1)[:, :min(k, v)]   # stable: ties -> low idx
    return jnp.take_along_axis(z, order, axis=1), order


def _check_against_dense(vals, idxs, h, w, k, valid, cap):
    dv, di = _dense_oracle(h, w, k, valid, cap)
    kd = dv.shape[1]
    np.testing.assert_allclose(np.asarray(vals[:, :kd]), np.asarray(dv),
                               rtol=1e-5, atol=1e-5)
    # indices must match exactly wherever the value is finite (tie order
    # included); -inf positions carry unspecified indices
    fin = np.isfinite(np.asarray(dv))
    np.testing.assert_array_equal(np.asarray(idxs[:, :kd])[fin],
                                  np.asarray(di)[fin])
    if k > kd:                      # k > V: tail is -inf by contract
        assert np.all(np.asarray(vals[:, kd:]) == -np.inf)


def _problem(b, d, v, quantize, seed):
    k1, k2 = jax.random.split(jax.random.PRNGKey(seed))
    h = jax.random.normal(k1, (b, d))
    w = jax.random.normal(k2, (v, d)) * 0.3
    if quantize:                    # force massive value ties
        h = jnp.round(h * 2) / 2
        w = jnp.round(w * 2) / 2
    return h, w


_GRID = [
    # b, d,  v,   k,  valid, cap,  quantize
    (4, 32, 333,  8,  300,   None, False),
    (1, 16, 100,  1,  100,   None, False),
    (3,  8,  50, 60,   10,   None, False),   # k > valid and k > V
    (5, 64, 520, 40,  517,   30.0, False),   # ragged vocab + softcap
    (6, 16, 200, 16,  200,   None, True),    # massive ties
    (2,  8, 130, 12,  64,    5.0,  True),    # ties + mask + softcap
    (8,  4,   3,  3,   3,    None, False),   # tiny vocab
]


@pytest.mark.parametrize("b,d,v,k,valid,cap,quantize", _GRID)
def test_pallas_topk_matches_dense(b, d, v, k, valid, cap, quantize):
    h, w = _problem(b, d, v, quantize, seed=b * 7 + k)
    vals, idxs = pallas_topk(h, w, k, valid_vocab=valid, logit_softcap=cap)
    assert vals.shape == idxs.shape == (b, k)
    _check_against_dense(vals, idxs, h, w, k, valid, cap)
    assert np.all(np.asarray(idxs) < max(valid, 1))


@pytest.mark.parametrize("b,d,v,k,valid,cap,quantize", _GRID)
def test_streaming_topk_matches_dense(b, d, v, k, valid, cap, quantize):
    """The pure-JAX oracle obeys the same contract, k > block_v and
    k > V included (the chunk top-k is clamped at min(k, block_v))."""
    h, w = _problem(b, d, v, quantize, seed=b * 11 + k)
    vals, idxs = streaming_topk(h, w, k, block_v=37, valid_vocab=valid,
                                logit_softcap=cap)
    _check_against_dense(vals, idxs, h, w, k, valid, cap)


def test_kernel_equals_jax_oracle_with_explicit_plan():
    """kernel == streaming_topk under a deliberately awkward tiling."""
    h = jax.random.normal(jax.random.PRNGKey(0), (5, 24))
    w = jax.random.normal(jax.random.PRNGKey(1), (300, 24))
    plan = BlockPlan(8, 128, tile_bytes(8, 128, 24))
    kv, ki = pallas_topk(h, w, 7, valid_vocab=290, logit_softcap=20.0,
                         plan=plan)
    ov, oi = streaming_topk(h, w, 7, block_v=64, valid_vocab=290,
                            logit_softcap=20.0)
    np.testing.assert_allclose(np.asarray(kv), np.asarray(ov), rtol=1e-5)
    np.testing.assert_array_equal(np.asarray(ki), np.asarray(oi))


def test_topk_col_offset_shards_merge():
    """TP shards: per-shard top-k with col_offset merges to the global."""
    h = jax.random.normal(jax.random.PRNGKey(0), (3, 16))
    w = jax.random.normal(jax.random.PRNGKey(1), (128, 16))
    k = 6
    full_v, full_i = pallas_topk(h, w, k)
    shard_v, shard_i = [], []
    for lo in (0, 64):
        sv, si = pallas_topk(h, w[lo:lo + 64], k, col_offset=lo,
                             valid_vocab=128)
        shard_v.append(sv)
        shard_i.append(si)
    mv = jnp.concatenate(shard_v, axis=1)
    mi = jnp.concatenate(shard_i, axis=1)
    gv, sel = jax.lax.top_k(mv, k)
    gi = jnp.take_along_axis(mi, sel, axis=1)
    np.testing.assert_allclose(np.asarray(gv), np.asarray(full_v),
                               rtol=1e-5)
    np.testing.assert_array_equal(np.asarray(gi), np.asarray(full_i))


if _HAVE_HYPOTHESIS:
    _SETTINGS = dict(max_examples=15, deadline=None)

    @given(b=st.integers(1, 6), d=st.sampled_from([4, 16, 33]),
           v=st.integers(3, 260), k=st.integers(1, 20),
           valid_frac=st.floats(0.1, 1.0),
           cap=st.sampled_from([None, 5.0, 30.0]),
           quantize=st.booleans(), seed=st.integers(0, 10_000))
    @settings(**_SETTINGS)
    def test_pallas_topk_matches_dense_fuzz(b, d, v, k, valid_frac, cap,
                                            quantize, seed):
        h, w = _problem(b, d, v, quantize, seed)
        valid = max(1, int(v * valid_frac))
        vals, idxs = pallas_topk(h, w, k, valid_vocab=valid,
                                 logit_softcap=cap)
        _check_against_dense(vals, idxs, h, w, k, valid, cap)
        assert np.all(np.asarray(idxs) < max(valid, 1))

    @given(b=st.integers(1, 4), v=st.integers(5, 150), k=st.integers(1, 12),
           block=st.integers(3, 70), seed=st.integers(0, 10_000))
    @settings(**_SETTINGS)
    def test_streaming_topk_matches_dense_fuzz(b, v, k, block, seed):
        h, w = _problem(b, 8, v, True, seed)
        vals, idxs = streaming_topk(h, w, k, block_v=block)
        _check_against_dense(vals, idxs, h, w, k, v, None)


# ---------------------------------------------------------------------------
# allowed-mask (constrained decoding) + return_lse (beam logprobs)
# ---------------------------------------------------------------------------


def _masked_dense(h, w, mask, valid, cap):
    """Dense logits with the allowed-mask AND valid-vocab filter applied
    (-inf outside) — the distribution the kernel must reproduce."""
    z = h.astype(jnp.float32) @ w.T.astype(jnp.float32)
    if cap is not None:
        z = cap * jnp.tanh(z / cap)
    v = w.shape[0]
    keep = (jnp.arange(v)[None, :] < valid) & (mask != 0)
    return jnp.where(keep, z, -jnp.inf)


def _rand_mask(b, v, frac, seed, ensure=0):
    rng = np.random.default_rng(seed)
    mask = (rng.random((b, v)) < frac).astype(np.int8)
    mask[:, ensure] = 1                      # never empty
    return jnp.asarray(mask)


@pytest.mark.parametrize("fn,kw", [
    (pallas_topk, {}),
    (streaming_topk, {"block_v": 37}),
])
def test_topk_allowed_mask_matches_masked_dense(fn, kw):
    h, w = _problem(4, 16, 130, False, seed=21)
    mask = _rand_mask(4, 130, 0.25, seed=22)
    vals, idxs = fn(h, w, 8, valid_vocab=100, logit_softcap=12.0,
                    allowed_mask=mask, **kw)
    z = _masked_dense(h, w, mask, 100, 12.0)
    dv, di = jax.lax.top_k(z, 8)
    fin = np.isfinite(np.asarray(dv))
    np.testing.assert_allclose(np.asarray(vals)[fin], np.asarray(dv)[fin],
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_array_equal(np.asarray(idxs)[fin],
                                  np.asarray(di)[fin])
    # every finite candidate is in the allowed set
    m = np.asarray(mask)
    for b in range(4):
        for j in np.flatnonzero(fin[b]):
            assert m[b, idxs[b, j]] == 1


@pytest.mark.parametrize("fn,kw", [
    (pallas_topk, {}),
    (streaming_topk, {"block_v": 64}),
])
def test_topk_full_mask_bit_identical_to_unmasked(fn, kw):
    h, w = _problem(3, 8, 90, True, seed=23)          # value ties
    ones = jnp.ones((3, 90), jnp.int8)
    v0, i0 = fn(h, w, 12, valid_vocab=80, **kw)
    v1, i1 = fn(h, w, 12, valid_vocab=80, allowed_mask=ones, **kw)
    np.testing.assert_array_equal(np.asarray(v0), np.asarray(v1))
    np.testing.assert_array_equal(np.asarray(i0), np.asarray(i1))


@pytest.mark.parametrize("fn,kw", [
    (pallas_topk, {}),
    (streaming_topk, {"block_v": 41}),
])
@pytest.mark.parametrize("masked", [False, True])
def test_topk_return_lse_matches_dense_logsumexp(fn, kw, masked):
    h, w = _problem(5, 16, 140, False, seed=24)
    mask = _rand_mask(5, 140, 0.4, seed=25) if masked else None
    vals, idxs, lse = fn(h, w, 6, valid_vocab=120, logit_softcap=9.0,
                         allowed_mask=mask, return_lse=True, **kw)
    z = _masked_dense(h, w,
                      mask if mask is not None else jnp.ones((5, 140)),
                      120, 9.0)
    want = jax.scipy.special.logsumexp(z, axis=-1)
    np.testing.assert_allclose(np.asarray(lse), np.asarray(want),
                               rtol=1e-5, atol=1e-5)
    # vals - lse are normalized logprobs: each row sums under 1
    logp = np.asarray(vals) - np.asarray(lse)[:, None]
    assert np.all(np.exp(logp[np.isfinite(logp)]) <= 1.0 + 1e-6)


def test_sample_tokens_singleton_mask_any_temperature():
    from repro.serve.sampler import sample_tokens
    h, w = _problem(4, 16, 64, False, seed=26)
    only = jnp.zeros((4, 64), jnp.int8).at[:, 17].set(1)
    for impl in ("pallas", "jax"):
        for temp, top_p in ((0.0, None), (0.7, None), (1.5, 0.9)):
            tok = sample_tokens(h, w, jax.random.PRNGKey(3),
                                temperature=temp, top_p=top_p,
                                impl=impl, allowed_mask=only)
            np.testing.assert_array_equal(np.asarray(tok), np.full(4, 17))


if _HAVE_HYPOTHESIS:
    @given(b=st.integers(1, 4), v=st.integers(8, 120),
           frac=st.floats(0.05, 0.9),
           temp=st.sampled_from([0.0, 0.3, 1.0, 2.5]),
           top_p=st.sampled_from([None, 0.5, 0.95]),
           impl=st.sampled_from(["pallas", "jax"]),
           seed=st.integers(0, 10_000))
    @settings(max_examples=20, deadline=None, derandomize=True)
    def test_masked_token_never_sampled_fuzz(b, v, frac, temp, top_p,
                                             impl, seed):
        """THE constrained-decoding property: no temperature / top-p /
        impl combination can ever emit a token outside the mask."""
        from repro.serve.sampler import sample_tokens
        h, w = _problem(b, 8, v, False, seed)
        mask = _rand_mask(b, v, frac, seed + 1, ensure=seed % v)
        tok = np.asarray(sample_tokens(
            h, w, jax.random.PRNGKey(seed), temperature=temp,
            top_p=top_p, impl=impl, allowed_mask=mask))
        m = np.asarray(mask)
        for i in range(b):
            assert m[i, tok[i]] == 1, (i, tok[i])

    @given(b=st.integers(1, 4), v=st.integers(6, 100),
           k=st.integers(1, 10), frac=st.floats(0.1, 1.0),
           seed=st.integers(0, 10_000))
    @settings(max_examples=15, deadline=None, derandomize=True)
    def test_topk_mask_lse_fuzz(b, v, k, frac, seed):
        """kernel == streaming oracle == dense top-k/logsumexp under a
        random mask, lse included (tie order exact)."""
        h, w = _problem(b, 8, v, True, seed)
        mask = _rand_mask(b, v, frac, seed + 7)
        kv, ki, kl = pallas_topk(h, w, k, allowed_mask=mask,
                                 return_lse=True)
        ov, oi, ol = streaming_topk(h, w, k, block_v=29,
                                    allowed_mask=mask, return_lse=True)
        fin = np.isfinite(np.asarray(ov))
        np.testing.assert_allclose(np.asarray(kv)[fin],
                                   np.asarray(ov)[fin], rtol=1e-5,
                                   atol=1e-5)
        np.testing.assert_array_equal(np.asarray(ki)[fin],
                                      np.asarray(oi)[fin])
        np.testing.assert_allclose(np.asarray(kl), np.asarray(ol),
                                   rtol=1e-5, atol=1e-5)
        z = _masked_dense(h, w, mask, v, None)
        want = jax.scipy.special.logsumexp(z, axis=-1)
        np.testing.assert_allclose(np.asarray(kl), np.asarray(want),
                                   rtol=1e-5, atol=1e-5)
