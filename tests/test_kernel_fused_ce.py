"""Pallas fused-CE kernels (interpret mode) vs the pure-jnp ref.py oracle
and the canonical autodiff loss.

Required per-kernel validation: sweep shapes/dtypes and assert_allclose
the forward stats and the backward kernel's dH and dW, in both of the
backward's orientations (dW or dH read-modify-written), against ref.py
and `tests/grad_oracle.py`.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import LossConfig, canonical_loss
from repro.core.streaming import _row_scale
from repro.core.windows import BlockPlan, choose_blocks, tile_bytes
from repro.kernels.fused_ce import kernel as K
from repro.kernels.fused_ce.ops import pallas_loss
from repro.kernels.fused_ce.ref import ref_stats, ref_grads

from grad_oracle import assert_grads_close, make_problem, oracle_grads

SHAPES = [
    # (n, d, v, bm, bv)
    (8, 32, 96, 8, 32),
    (50, 64, 700, 16, 256),       # ragged rows + vocab vs blocks
    (128, 128, 512, 64, 128),
    (17, 48, 130, 8, 128),        # bv > v
]
DTYPES = [jnp.float32, jnp.bfloat16]


def _problem(n, d, v, dtype, seed=0):
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(seed), 3)
    h = (jax.random.normal(k1, (n, d)) * 0.7).astype(dtype)
    w = (jax.random.normal(k2, (v, d)) * 0.07).astype(dtype)
    y = jax.random.randint(k3, (n,), 0, v)
    return h, w, y


@pytest.mark.parametrize("dtype", DTYPES, ids=("f32", "bf16"))
@pytest.mark.parametrize("shape", SHAPES, ids=[str(s[:3]) for s in SHAPES])
def test_fwd_kernel_vs_ref(shape, dtype):
    n, d, v, bm, bv = shape
    h, w, y = _problem(n, d, v, dtype)
    cfg = LossConfig(valid_vocab=v - 3)
    plan = BlockPlan(bm, bv, 0)
    lse, zt, zs = K.fwd_stats(h, w, y, cfg, plan=plan)
    rl, rt, rs = ref_stats(h, w, y, cfg)
    tol = 1e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(lse, rl, rtol=tol, atol=tol)
    np.testing.assert_allclose(zt, rt, rtol=tol, atol=tol)
    np.testing.assert_allclose(zs, rs, rtol=5 * tol, atol=5 * tol)


@pytest.mark.parametrize("shape", SHAPES[:3], ids=[str(s[:3])
                                                   for s in SHAPES[:3]])
def test_bwd_kernels_vs_ref(shape):
    n, d, v, bm, bv = shape
    h, w, y = _problem(n, d, v, jnp.float32)
    cfg = LossConfig(valid_vocab=v - 1, label_smoothing=0.05, z_loss=1e-4)
    lse, _, _ = ref_stats(h, w, y, cfg)
    gamma = jax.random.uniform(jax.random.PRNGKey(7), (n,)) / n
    p_coeff = gamma * (1.0 + 2e-4 * lse)
    dh, dw = K.bwd_grads(h, w, y, lse, gamma, p_coeff, cfg,
                         plan=BlockPlan(bm, bv, 0))
    rdh, rdw = ref_grads(h, w, y, lse, gamma, p_coeff, cfg)
    np.testing.assert_allclose(dh, rdh, rtol=3e-4, atol=1e-6)
    np.testing.assert_allclose(dw, rdw, rtol=3e-4, atol=1e-6)


@pytest.mark.parametrize("feature", ["plain", "smooth", "zloss", "softcap"])
def test_pallas_loss_end_to_end_grads(feature):
    h, w, y = _problem(40, 64, 300, jnp.float32, seed=3)
    kw = {"plain": {}, "smooth": {"label_smoothing": 0.1},
          "zloss": {"z_loss": 1e-4}, "softcap": {"logit_softcap": 20.0}}
    cfg = LossConfig(block_v=128, **kw[feature])
    ref = canonical_loss(h, w, y, cfg)
    out = pallas_loss(h, w, y, cfg)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-5)
    ga = jax.grad(lambda h, w: canonical_loss(h, w, y, cfg), (0, 1))(h, w)
    gb = jax.grad(lambda h, w: pallas_loss(h, w, y, cfg), (0, 1))(h, w)
    np.testing.assert_allclose(ga[0], gb[0], rtol=3e-4, atol=1e-5)
    np.testing.assert_allclose(ga[1], gb[1], rtol=3e-4, atol=1e-5)


def test_kernel_col_offset_tp_merge():
    """The kernel computes correct partial stats for a TP vocab shard."""
    n, d, v = 24, 32, 256
    h, w, y = _problem(n, d, v, jnp.float32, seed=5)
    y = jnp.clip(y, 0, 249)          # targets must be < valid_vocab
    cfg = LossConfig(valid_vocab=250)
    rl, rt, rs = ref_stats(h, w, y, cfg)
    plan = BlockPlan(8, 64, 0)
    l1, t1, s1 = K.fwd_stats(h, w[:128], y, cfg, plan=plan,
                             col_offset=0, total_valid=250)
    l2, t2, s2 = K.fwd_stats(h, w[128:], y, cfg, plan=plan,
                             col_offset=128, total_valid=250)
    m = jnp.maximum(l1, l2)
    lse = m + jnp.log(jnp.exp(l1 - m) + jnp.exp(l2 - m))
    np.testing.assert_allclose(lse, rl, rtol=1e-5)
    np.testing.assert_allclose(t1 + t2, rt, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(s1 + s2, rs, rtol=1e-4)


def test_window_block_plan_fits_vmem():
    """choose_blocks (the paper's window-size knob) stays in VMEM budget
    and hardware-aligned across representative problem sizes."""
    for n, v, d in [(1, 262144, 4096), (32768, 32768, 4096),
                    (1024, 151936, 1024), (128, 256206, 12288)]:
        plan = choose_blocks(n, v, d, in_bytes=2)
        assert plan.block_rows % 8 == 0
        assert plan.block_v % 128 == 0
        assert tile_bytes(plan.block_rows, plan.block_v, d) \
            <= int(16 * 1024 * 1024 * 0.55) + 1


# Plans of the backward over 50 rows and 300 vocab columns, each with pad
# rows and pad vocab columns.  The kernel read-modify-writes the smaller
# gradient block (dW's when bv <= bm), unless that gradient is one block
# alone; an inner axis of two blocks takes the DMA path with a lag of one.
BWD_PLANS = {
    "rmw_dw": BlockPlan(32, 32, 0),             # grid (2, 10)
    "rmw_dh": BlockPlan(8, 128, 0),             # grid (7, 3)
    "one_row_block": BlockPlan(64, 64, 0),      # grid (1, 5), rmw dW
    "one_vocab_block": BlockPlan(8, 384, 0),    # grid (7, 1), rmw dH
    "two_row_blocks": BlockPlan(32, 256, 0),    # grid (2, 2), rmw dH
}
BWD_FEATURES = {
    "plain": {}, "softcap": {"logit_softcap": 12.0},
    "zloss": {"z_loss": 1e-4}, "smooth": {"label_smoothing": 0.1},
}
_BWD_N, _BWD_V, _BWD_D, _BWD_VALID = 50, 300, 32, 290


def _bwd_problem(dtype, seed=0):
    """Every fifth row ignore-masked; targets below the valid vocab."""
    return make_problem(_BWD_N, _BWD_V, _BWD_D, dtype, seed=seed,
                        valid=_BWD_VALID)


# bf16 inputs take one bf16 MXU pass, the gradient tile rounded to bf16
# (DESIGN.md §2.1): the oracle's absolute tolerance covers that rounding
# at the weight a row has in the training cells' mean over 4 x 4096
# tokens, which the bf16 cases give each row; f32 cases take the mean
# over this problem's rows.
_CELL_ROWS = 4 * 4096


def _bwd_case(h, w, y, cfg):
    """(lse, gamma, p_coeff) as the loss's backward hands them to the
    kernel, and the canonical loss's f32 autodiff (dH, dW) at the same
    upstream gradient."""
    keep = jnp.sum(y != cfg.ignore_index).astype(jnp.float32)
    gbar = (jnp.float32(1.0) if h.dtype == jnp.float32
            else keep / _CELL_ROWS)
    lse, _, _ = ref_stats(h, w, y, cfg)
    gamma = _row_scale(gbar, y, cfg)
    p_coeff = gamma * (1.0 + 2.0 * jnp.float32(cfg.z_loss) * lse)
    oracle = oracle_grads(h.astype(jnp.float32), w.astype(jnp.float32), y,
                          cfg)
    return (lse, gamma, p_coeff), tuple(g * gbar for g in oracle)


@pytest.mark.parametrize("dtype", DTYPES, ids=("f32", "bf16"))
@pytest.mark.parametrize("feature", BWD_FEATURES)
@pytest.mark.parametrize("plan", BWD_PLANS)
def test_bwd_kernel_vs_oracle(plan, feature, dtype):
    """The kernel's f32 dH and dW against the canonical loss's f32
    autodiff, within the oracle's tolerance: ignore rows, pad rows, pad
    and invalid vocab columns in every case."""
    h, w, y = _bwd_problem(dtype, seed=len(feature))
    cfg = LossConfig(valid_vocab=_BWD_VALID, **BWD_FEATURES[feature])
    inputs, oracle = _bwd_case(h, w, y, cfg)
    got = K.bwd_grads(h, w, y, *inputs, cfg, plan=BWD_PLANS[plan])
    assert_grads_close(oracle, got)


@pytest.mark.parametrize("dtype", DTYPES, ids=("f32", "bf16"))
@pytest.mark.parametrize("plan", ["rmw_dw", "rmw_dh"])
def test_bwd_kernel_shard_slices_vs_oracle(plan, dtype):
    """Two vocabulary shards, each a `col_offset`/`total_valid` slice of
    W: their dH partials sum, and their dW slices stack, to the canonical
    loss's gradients; the second shard holds pad columns past the valid
    vocabulary."""
    h, w, y = _bwd_problem(dtype, seed=3)
    cfg = LossConfig(valid_vocab=_BWD_VALID, label_smoothing=0.05,
                     z_loss=1e-4, logit_softcap=15.0)
    inputs, oracle = _bwd_case(h, w, y, cfg)
    parts = [K.bwd_grads(h, w[lo:hi], y, *inputs, cfg,
                         plan=BWD_PLANS[plan], col_offset=lo,
                         total_valid=_BWD_VALID)
             for lo, hi in ((0, 160), (160, _BWD_V))]
    got = (parts[0][0] + parts[1][0],
           jnp.concatenate([parts[0][1], parts[1][1]]))
    assert_grads_close(oracle, got)


@pytest.mark.parametrize("plan", ["rmw_dw", "rmw_dh", "two_row_blocks"])
def test_bwd_kernel_bitwise_repeatable(plan):
    """Two identical calls give the same dH and dW bits."""
    h, w, y = _bwd_problem(jnp.float32, seed=5)
    cfg = LossConfig(valid_vocab=_BWD_VALID, z_loss=1e-4)
    args = (h, w, y) + _bwd_case(h, w, y, cfg)[0] + (cfg,)
    a = K.bwd_grads(*args, plan=BWD_PLANS[plan])
    b = K.bwd_grads(*args, plan=BWD_PLANS[plan])
    for x, z in zip(a, b):
        np.testing.assert_array_equal(np.asarray(x).view(np.uint32),
                                      np.asarray(z).view(np.uint32))
