"""Pallas fused-CE kernels (interpret mode) vs the pure-jnp ref.py oracle
and the canonical autodiff loss, and the tile plan every vocabulary
kernel runs.

Required per-kernel validation: sweep shapes/dtypes and assert_allclose
the forward stats and the backward kernel's dH and dW, in both of the
backward's orientations (dW or dH read-modify-written), against ref.py
and `tests/grad_oracle.py`.

The plan is a function of the shape (DESIGN.md §3.2): the entry points
are traced over abstract inputs, nothing allocated or run, and the
`pallas_call` they stage must carry `choose_blocks`' tiles.
"""

import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.analysis.lint.jaxpr import walk
from repro.core import LossConfig, canonical_loss, streaming_loss
from repro.core.streaming import _row_scale
from repro.core.windows import (BlockPlan, VMEM_BYTES, bwd_tile_bytes,
                                choose_blocks, tile_bytes, _DEFAULT_BUDGET)
from repro.kernels.fused_ce import kernel as K
from repro.kernels.fused_ce.ops import pallas_loss
from repro.kernels.fused_ce.ref import ref_stats, ref_grads
from repro.kernels.sample_topk import pallas_topk
from repro.kernels.score_tokens import pallas_score_tokens
from repro.models.attention import AttnConfig, attention_layer, init_attention

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


# ---------------------------------------------------------------------------
# choose_blocks edge cases
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("n", [1, 3, 7])
def test_choose_blocks_tiny_decode_rows(n):
    """decode shapes (B*T == B): rows tile floors at one sublane group."""
    plan = choose_blocks(n, 262144, 4096, in_bytes=2)
    assert plan.block_rows == 8
    assert plan.block_v % 128 == 0
    assert tile_bytes(plan.block_rows, plan.block_v, 4096) <= \
        _DEFAULT_BUDGET


def test_choose_blocks_vmem_shrink_loop():
    """an unsatisfiable budget bottoms out at the aligned floor tiles
    instead of looping forever or misaligning."""
    plan = choose_blocks(4096, 262144, 4096, in_bytes=2,
                         vmem_budget=200_000)
    assert (plan.block_rows, plan.block_v) == (8, 128)


def test_choose_blocks_fits_generous_budget():
    plan = choose_blocks(4096, 262144, 1024, in_bytes=2)
    assert tile_bytes(plan.block_rows, plan.block_v, 1024) <= \
        _DEFAULT_BUDGET
    assert plan.block_rows % 8 == 0 and plan.block_v % 128 == 0


# ---------------------------------------------------------------------------
# an explicit plan, threaded end to end
# ---------------------------------------------------------------------------


def _small_problem(seed=0):
    """16 rows of d=32 against a 256-row vocabulary, in f32."""
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(seed), 3)
    h = jax.random.normal(k1, (16, 32)) * 0.5
    w = jax.random.normal(k2, (256, 32)) * 0.05
    y = jax.random.randint(k3, (16,), 0, 256)
    return h, w, y


def test_pallas_and_streaming_accept_explicit_plan():
    h, w, y = _small_problem()
    cfg = LossConfig(block_v=64)
    plan = BlockPlan(16, 128, 0)        # not choose_blocks' (16, 256)
    base = streaming_loss(h, w, y, cfg)
    via_stream = streaming_loss(h, w, y, cfg, plan=plan)
    via_pallas = pallas_loss(h, w, y, cfg, plan=plan)
    np.testing.assert_allclose(float(base), float(via_stream), rtol=1e-5)
    np.testing.assert_allclose(float(base), float(via_pallas), rtol=1e-5)


def test_pallas_loss_grads_with_explicit_plan():
    h, w, y = _small_problem()
    cfg = LossConfig(block_v=64)
    plan = BlockPlan(8, 128, 0)
    ref = jax.grad(lambda h, w: streaming_loss(h, w, y, cfg), (0, 1))(h, w)
    got = jax.grad(lambda h, w: pallas_loss(h, w, y, cfg, plan=plan),
                   (0, 1))(h, w)
    np.testing.assert_allclose(np.asarray(ref[0]), np.asarray(got[0]),
                               rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(np.asarray(ref[1]), np.asarray(got[1]),
                               rtol=2e-5, atol=2e-5)


# ---------------------------------------------------------------------------
# the plan each entry point traces: choose_blocks of the shape
# ---------------------------------------------------------------------------

_S = jax.ShapeDtypeStruct

# entry -> (function of (h, w, y), the kernel it stages, and the places of
# the (rows, d) H block and the (vocab, d) W block among its inputs)
PLAN_ENTRIES = {
    "loss_fwd": (lambda h, w, y: pallas_loss(h, w, y), "fused_ce_fwd", 2, 3),
    "loss_bwd": (jax.grad(lambda h, w, y: pallas_loss(h, w, y), (0, 1)),
                 "fused_ce_bwd", 5, 6),
    "topk": (lambda h, w, y: pallas_topk(h, w, 4), "sample_topk", 1, 2),
    "score": (lambda h, w, y: pallas_score_tokens(h, w, y[:, None]),
              "score_tokens", 2, 3),
}
# (rows, vocab, d, dtype): the training cells, two decode batches at the
# cells' vocabulary, and a vocabulary smaller than choose_blocks' widest
# tile
PLAN_SHAPES = {
    "cell_f32": (16384, 151936, 1024, jnp.float32),
    "cell_bf16": (16384, 151936, 1024, jnp.bfloat16),
    "decode_1": (1, 151936, 1024, jnp.bfloat16),
    "decode_8": (8, 151936, 1024, jnp.bfloat16),
    "vocab_512": (40, 512, 128, jnp.float32),
}


def _pallas_calls(jaxpr):
    """The `pallas_call` equations of a jaxpr and its sub-jaxprs."""
    return [e for _, e in walk(jaxpr) if e.primitive.name == "pallas_call"]


def _traced_kernel(entry, n, v, d, dtype):
    """(equation, grid mapping, bm, bv) of the kernel `entry` stages for
    an (n, d) x (v, d) problem, traced over abstract inputs."""
    fn, name, h_at, w_at = PLAN_ENTRIES[entry]
    jaxpr = jax.make_jaxpr(fn)(_S((n, d), dtype), _S((v, d), dtype),
                               _S((n,), jnp.int32))
    eqn, = [e for e in _pallas_calls(jaxpr) if e.params["name"] == name]
    gm = eqn.params["grid_mapping"]
    blocks = [tuple(getattr(b, "block_size", b) for b in m.block_shape)
              for m in gm.block_mappings]
    (bm, h_d), (bv, w_d) = blocks[h_at], blocks[w_at]
    assert h_d == w_d == d
    return eqn, gm, bm, bv


@pytest.mark.parametrize("shape", PLAN_SHAPES)
@pytest.mark.parametrize("entry", PLAN_ENTRIES)
def test_entry_point_runs_the_shape_plan(entry, shape):
    """The staged kernel tiles by `choose_blocks` at the hidden states'
    itemsize, on an (8, 128)-aligned grid, and its VMEM model fits the
    scoped limit the kernel asks for (the compiler's default when it
    asks for none)."""
    n, v, d, dtype = PLAN_SHAPES[shape]
    in_bytes = jnp.dtype(dtype).itemsize
    eqn, gm, bm, bv = _traced_kernel(entry, n, v, d, dtype)
    assert (bm, bv) == choose_blocks(n, v, d, in_bytes=in_bytes).shape
    assert bm % 8 == 0 and bv % 128 == 0
    grid = (-(-n // bm), -(-v // bv))
    if entry == "loss_bwd":
        rmw_dw = gm.grid == grid
        assert rmw_dw or gm.grid == grid[::-1]
        assert gm.scratch_avals[0].shape == (3, bv if rmw_dw else bm, d)
        model = bwd_tile_bytes(bm, bv, d, in_bytes, rmw_dw=rmw_dw)
    else:
        assert gm.grid == grid
        model = tile_bytes(bm, bv, d, in_bytes)
    limit = eqn.params["compiler_params"]["mosaic_tpu"].vmem_limit_bytes
    assert model <= (limit or VMEM_BYTES)


@pytest.mark.parametrize("dtype,plan,rmw", [
    (jnp.float32, (512, 256), "dW"),
    (jnp.bfloat16, (512, 1024), "dH"),
], ids=("f32", "bf16"))
def test_cell_plan_pinned(dtype, plan, rmw):
    """The training cells' loss runs (512, 256) tiles in f32, dW read,
    modified and written, and (512, 1024) in bf16, dH read, modified and
    written — forward and backward alike, over Qwen3-0.6B's vocabulary
    and the 152064 rows the program pads its lm_head to."""
    n, d = 16384, 1024
    for v in (151936, 152064):
        assert choose_blocks(
            n, v, d, in_bytes=jnp.dtype(dtype).itemsize).shape == plan
        for entry in ("loss_fwd", "loss_bwd"):
            _, gm, bm, bv = _traced_kernel(entry, n, v, d, dtype)
            assert (bm, bv) == plan
        # the backward's three-slot VMEM buffer holds the read-modify-
        # written gradient's blocks: dW's (bv, d) or dH's (bm, d)
        num_r, num_v = -(-n // bm), -(-v // bv)
        if rmw == "dW":
            assert gm.scratch_avals[0].shape == (3, bv, d)
            assert gm.grid == (num_r, num_v)
        else:
            assert gm.scratch_avals[0].shape == (3, bm, d)
            assert gm.grid == (num_v, num_r)


def _paged_decode_grid(b, nkv, hd, bs, nb):
    """Grid of the paged-attention kernel a one-token paged decode step
    stages, traced over abstract params and cache."""
    cfg = AttnConfig(d_model=2 * nkv * hd, num_heads=2 * nkv,
                     num_kv_heads=nkv, head_dim=hd, paged_impl="pallas")
    params = jax.eval_shape(
        lambda: init_attention(jax.random.PRNGKey(0), cfg))
    pool = _S((b * nb + 1, bs, nkv, hd), jnp.float32)
    cache = {"kp": pool, "vp": pool, "table": _S((b, nb), jnp.int32),
             "len": _S((b,), jnp.int32)}
    jaxpr = jax.make_jaxpr(
        lambda p, x, c: attention_layer(p, x, cfg, cache=c))(
            params, _S((b, 1, cfg.d_model), jnp.float32), cache)
    eqn, = _pallas_calls(jaxpr)
    return eqn.params["grid_mapping"].grid


@pytest.mark.parametrize("entry", [*PLAN_ENTRIES, "paged"])
def test_plan_ignores_outside_state(entry, tmp_path, monkeypatch):
    """A block-plan file in the format an earlier empirical tuner wrote,
    at both places it was read from (`$REPRO_TUNING_CACHE` and
    `~/.cache/repro/blockplans.json`), names other tiles for the shape
    and four pages a step: the traced kernels still run `choose_blocks`'
    plan, and paged decode one page a step."""
    n, v, d = 64, 512, 128
    b, nkv, hd, bs, nb = 2, 2, 16, 8, 4
    backend = jax.default_backend()
    stale = {"block_rows": 8, "block_v": 128, "vmem_bytes": 0, "us": 1.0}
    entries = {f"{n}x{v}x{d}:float32:{backend}{op}": stale
               for op in ("", ":topk4", ":score1")}
    entries[f"{b}x{nb * bs}x{nkv * hd}:float32:{backend}:pattn{bs}"] = \
        dict(stale, block_v=nb * bs)
    path = tmp_path / ".cache" / "repro" / "blockplans.json"
    path.parent.mkdir(parents=True)
    path.write_text(json.dumps({"version": 1, "entries": entries}))
    monkeypatch.setenv("HOME", str(tmp_path))
    monkeypatch.setenv("REPRO_TUNING_CACHE", str(path))
    if entry == "paged":
        assert _paged_decode_grid(b, nkv, hd, bs, nb) == (b, nb)
        return
    _, _, bm, bv = _traced_kernel(entry, n, v, d, jnp.float32)
    assert (bm, bv) == choose_blocks(n, v, d, in_bytes=4).shape == (64, 512)
