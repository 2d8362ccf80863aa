"""Token-scoring Pallas kernel: dense-oracle equivalence + plan machinery.

The dense oracle is log_softmax of the masked (softcapped) logits,
gathered at the candidate ids; the kernel contract covers duplicate
candidates (ties), out-of-range / padded ids (-inf), candidate counts
exceeding the vocab tile (P > block_v), ragged shapes, and shard merge
via col_offset.  The pure-JAX `streaming_score` is held to the same
contract so either can stand in for the other.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.windows import BlockPlan, tile_bytes
from repro.kernels.score_tokens import (pallas_score_tokens, score_stats,
                                        streaming_score)

try:
    from hypothesis import given, settings, strategies as st
    _HAVE_HYPOTHESIS = True
except ImportError:                     # pragma: no cover - 'test' extra
    _HAVE_HYPOTHESIS = False


def _dense_oracle(h, w, ids, valid, cap):
    z = h.astype(jnp.float32) @ w.T.astype(jnp.float32)
    if cap is not None:
        z = cap * jnp.tanh(z / cap)
    v = w.shape[0]
    z = jnp.where(jnp.arange(v)[None, :] < valid, z, -jnp.inf)
    lse = jax.nn.logsumexp(z, axis=-1)
    gathered = jnp.take_along_axis(z, jnp.clip(ids, 0, v - 1), axis=1)
    ok = (ids >= 0) & (ids < valid)
    return jnp.where(ok, gathered - lse[:, None], -jnp.inf), lse


def _problem(n, d, v, p, seed, frac_invalid=0.25):
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(seed), 3)
    h = jax.random.normal(k1, (n, d))
    w = jax.random.normal(k2, (v, d)) * 0.3
    # ids deliberately spill outside [0, v): invalid rows score -inf
    lo = -max(1, int(v * frac_invalid))
    ids = jax.random.randint(k3, (n, p), lo, v + max(1, int(v * 0.2)),
                             jnp.int32)
    return h, w, ids


_GRID = [
    # n, d,  v,   p,  valid, cap
    (4, 32, 333,  1,  300,   None),     # verification shape (P=1)
    (1, 16, 100,  5,  100,   30.0),     # batch 1 + softcap
    (3,  8,  50, 200,  17,   None),     # P > block_v, tiny valid vocab
    (5, 64, 520,  8,  517,   5.0),      # ragged vocab + softcap
    (8,  4,   3,   3,   3,   None),     # tiny vocab
    (6, 16, 200,  4,  200,   None),
]


@pytest.mark.parametrize("n,d,v,p,valid,cap", _GRID)
def test_pallas_score_matches_dense(n, d, v, p, valid, cap):
    h, w, ids = _problem(n, d, v, p, seed=n * 13 + p)
    logp, lse = pallas_score_tokens(h, w, ids, valid_vocab=valid,
                                    logit_softcap=cap)
    dl, dlse = _dense_oracle(h, w, ids, valid, cap)
    assert logp.shape == (n, p)
    np.testing.assert_allclose(np.asarray(logp), np.asarray(dl),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(lse), np.asarray(dlse),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("n,d,v,p,valid,cap", _GRID)
def test_streaming_score_matches_dense(n, d, v, p, valid, cap):
    h, w, ids = _problem(n, d, v, p, seed=n * 17 + p)
    logp, lse = streaming_score(h, w, ids, block_v=37, valid_vocab=valid,
                                logit_softcap=cap)
    dl, dlse = _dense_oracle(h, w, ids, valid, cap)
    np.testing.assert_allclose(np.asarray(logp), np.asarray(dl),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(lse), np.asarray(dlse),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("impl", ["pallas", "jax"])
def test_temperature_scales_after_softcap(impl):
    """T-scaled scoring == log softmax(cap*tanh(z/cap)/T) gathered —
    the distribution the sampler draws from, in the sampler's order
    (cap first, then 1/T)."""
    h = jax.random.normal(jax.random.PRNGKey(0), (4, 16)) * 2
    w = jax.random.normal(jax.random.PRNGKey(1), (80, 16))
    ids = jax.random.randint(jax.random.PRNGKey(2), (4, 3), 0, 80,
                             jnp.int32)
    cap, temp = 8.0, 0.7
    z = cap * jnp.tanh((h @ w.T) / cap) / temp
    want = jnp.take_along_axis(jax.nn.log_softmax(z, axis=-1), ids, axis=1)
    fn = pallas_score_tokens if impl == "pallas" else streaming_score
    kwargs = {} if impl == "pallas" else {"block_v": 37}
    logp, _ = fn(h, w, ids, logit_softcap=cap, temperature=temp, **kwargs)
    np.testing.assert_allclose(np.asarray(logp), np.asarray(want),
                               rtol=1e-5, atol=1e-5)
    # temperature None / <= 0 scores unscaled
    lp_none, _ = fn(h, w, ids, logit_softcap=cap, **kwargs)
    lp_zero, _ = fn(h, w, ids, logit_softcap=cap, temperature=0.0,
                    **kwargs)
    np.testing.assert_allclose(np.asarray(lp_none), np.asarray(lp_zero),
                               rtol=1e-6)


def test_duplicate_candidates_score_identically():
    """Ties: the same id in several candidate slots gets the same logp."""
    h = jax.random.normal(jax.random.PRNGKey(0), (3, 16))
    w = jax.random.normal(jax.random.PRNGKey(1), (90, 16))
    ids = jnp.tile(jnp.array([[7], [11], [42]], jnp.int32), (1, 6))
    logp, _ = pallas_score_tokens(h, w, ids)
    np.testing.assert_allclose(np.asarray(logp),
                               np.asarray(logp[:, :1]) @ np.ones((1, 6)),
                               rtol=1e-6)


def test_vector_ids_squeeze():
    """(N,) ids round-trip as (N,) logp — the verification call shape."""
    h = jax.random.normal(jax.random.PRNGKey(0), (5, 8))
    w = jax.random.normal(jax.random.PRNGKey(1), (64, 8))
    ids = jnp.arange(5, dtype=jnp.int32) * 3
    logp, lse = pallas_score_tokens(h, w, ids)
    assert logp.shape == (5,) and lse.shape == (5,)
    lp2, _ = pallas_score_tokens(h, w, ids[:, None])
    np.testing.assert_array_equal(np.asarray(logp), np.asarray(lp2[:, 0]))


def test_kernel_equals_jax_oracle_with_explicit_plan():
    """kernel == streaming_score under a deliberately awkward tiling
    (padded rows + padded vocab columns never leak into real outputs)."""
    h = jax.random.normal(jax.random.PRNGKey(0), (5, 24))
    w = jax.random.normal(jax.random.PRNGKey(1), (300, 24))
    ids = jax.random.randint(jax.random.PRNGKey(2), (5, 3), 0, 300,
                             jnp.int32)
    plan = BlockPlan(8, 128, tile_bytes(8, 128, 24))
    kl, klse = pallas_score_tokens(h, w, ids, valid_vocab=290,
                                   logit_softcap=20.0, plan=plan)
    ol, olse = streaming_score(h, w, ids, block_v=64, valid_vocab=290,
                               logit_softcap=20.0)
    np.testing.assert_allclose(np.asarray(kl), np.asarray(ol), rtol=1e-5)
    np.testing.assert_allclose(np.asarray(klse), np.asarray(olse),
                               rtol=1e-5)


def test_score_col_offset_shards_merge():
    """TP shards: per-shard (lse, z_cand) with col_offset merge to the
    full-vocab result — psum the candidate logits, logsumexp the lses."""
    h = jax.random.normal(jax.random.PRNGKey(0), (3, 16))
    w = jax.random.normal(jax.random.PRNGKey(1), (128, 16))
    ids = jax.random.randint(jax.random.PRNGKey(2), (3, 4), 0, 128,
                             jnp.int32)
    full_lp, full_lse = pallas_score_tokens(h, w, ids)
    lses, zts = [], []
    for lo in (0, 64):
        lse_s, zt_s = score_stats(h, w[lo:lo + 64], ids, col_offset=lo,
                                  valid_vocab=128)
        lses.append(lse_s)
        zts.append(zt_s)
    lse = jnp.logaddexp(*lses)              # logsumexp merge
    zt = zts[0] + zts[1]                    # psum: each id hits one shard
    np.testing.assert_allclose(np.asarray(lse), np.asarray(full_lse),
                               rtol=1e-5)
    np.testing.assert_allclose(np.asarray(zt - lse[:, None]),
                               np.asarray(full_lp), rtol=1e-5, atol=1e-5)


if _HAVE_HYPOTHESIS:
    _SETTINGS = dict(max_examples=15, deadline=None)

    @given(n=st.integers(1, 6), d=st.sampled_from([4, 16, 33]),
           v=st.integers(3, 260), p=st.integers(1, 20),
           valid_frac=st.floats(0.1, 1.0),
           cap=st.sampled_from([None, 5.0, 30.0]),
           seed=st.integers(0, 10_000))
    @settings(**_SETTINGS)
    def test_pallas_score_matches_dense_fuzz(n, d, v, p, valid_frac, cap,
                                             seed):
        h, w, ids = _problem(n, d, v, p, seed)
        valid = max(1, int(v * valid_frac))
        logp, lse = pallas_score_tokens(h, w, ids, valid_vocab=valid,
                                        logit_softcap=cap)
        dl, dlse = _dense_oracle(h, w, ids, valid, cap)
        np.testing.assert_allclose(np.asarray(logp), np.asarray(dl),
                                   rtol=1e-4, atol=1e-4)
        np.testing.assert_allclose(np.asarray(lse), np.asarray(dlse),
                                   rtol=1e-4, atol=1e-4)
