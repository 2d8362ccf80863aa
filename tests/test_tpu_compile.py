"""The main path's Pallas kernels compile for a TPU v5e at real widths.

Nothing runs here.  The TPU compiler installed with jaxlib compiles for a
chip that is described and not attached (topology ``v5e:2x2``, one of its
devices), at the published widths of qwen3-0.6b: d=1024, V=151936, a
training batch of 8 x 1024 rows, a decode batch of 8, and the training
cell's attention core (4 x 4096 tokens, 16/8 heads).  It refuses what
interpret mode accepts: blocks that break Mosaic's (8, 128) tiling rule
and kernels that need more scoped VMEM than they ask for.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, and test workers import every
test file.  The persistent compilation cache is off around these compiles
(an entry written for a described chip cannot be read back without one).
"""

from __future__ import annotations

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.types import LossConfig
from repro.core.windows import choose_blocks
from repro.kernels import flash_attn
from repro.kernels.fused_ce import kernel as fused_ce
from repro.kernels.paged_attn.kernel import pallas_paged_attention
from repro.kernels.pallas_utils import tpu_kernels
from repro.kernels.sample_topk.kernel import topk_scores
from repro.kernels.score_tokens.kernel import score_stats

D, V = 1024, 151936                 # qwen3-0.6b hidden width and vocab
N_TRAIN = 8 * 1024                  # global batch 8 x sequence 1024
B_DECODE = 8
NQ, NKV, HD = 16, 8, 128            # qwen3-0.6b attention heads
BLOCK, MAX_LEN = 16, 256            # paged KV: tokens per block, per slot
B_ATTN, T_ATTN = 4, 4096            # the training cell's attention batch


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:      # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _compile(fn, sharding, shapes, kernels):
    """Compile `fn` for the described chip and check that its program
    calls exactly the named Pallas `kernels`."""
    args = [jax.ShapeDtypeStruct(s, dt, sharding=sharding)
            for s, dt in shapes]
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert sorted(tpu_kernels(text)) == sorted(kernels)


_EXACT = LossConfig(valid_vocab=V)
_FILTERED = LossConfig(valid_vocab=V, grad_filter_eps=1e-6)
_Y = ((N_TRAIN,), jnp.int32)
_ROW = ((N_TRAIN,), jnp.float32)
_BWD = ["fused_ce_dh", "fused_ce_dw"]


def _fwd(cfg, plan, stats):
    return lambda h, w, y: fused_ce.fwd_stats(
        h, w, y, cfg, plan=plan, interpret=False, return_tile_stats=stats)


def _bwd(cfg, plan):
    def fn(h, w, y, lse, gamma, pc, *tmax):
        return fused_ce.bwd_grads(
            h, w, y, lse, gamma, pc, cfg, plan=plan, interpret=False,
            tile_stats=tmax[0] if tmax else None)
    return fn


def _fused_ce_cases():
    """(fn, shapes, kernels) for bf16 training inputs, every variant, and
    f32 inputs, whose dots run at f32 precision."""
    cases = []
    for dt in (jnp.bfloat16, jnp.float32):
        plan = choose_blocks(N_TRAIN, V, D, in_bytes=jnp.dtype(dt).itemsize)
        grid = (-(-N_TRAIN // plan.block_rows), -(-V // plan.block_v))
        h, w = ((N_TRAIN, D), dt), ((V, D), dt)
        bwd = (h, w, _Y, _ROW, _ROW, _ROW)
        name = jnp.dtype(dt).name
        cases += [
            pytest.param(_fwd(_EXACT, plan, False), (h, w, _Y),
                         ["fused_ce_fwd"], id=f"fwd_{name}"),
            pytest.param(_bwd(_EXACT, plan), bwd, _BWD, id=f"bwd_{name}")]
        if dt is jnp.bfloat16:
            cases += [
                pytest.param(_fwd(_FILTERED, plan, True), (h, w, _Y),
                             ["fused_ce_fwd"], id="fwd_tile_stats"),
                pytest.param(_bwd(_FILTERED, plan),
                             bwd + ((grid, jnp.float32),), _BWD,
                             id="bwd_filtered")]
    return cases


@pytest.mark.parametrize("fn,shapes,kernels", _fused_ce_cases())
def test_fused_ce_compiles(one_chip, fn, shapes, kernels):
    _compile(fn, one_chip, shapes, kernels)


_HD = ((B_DECODE, D), jnp.bfloat16)
_W = ((V, D), jnp.bfloat16)
_SCALE = ((V,), jnp.float32)


@pytest.mark.parametrize("fn,shapes", [
    pytest.param(lambda h, w: topk_scores(h, w, 1, interpret=False),
                 (_HD, _W), id="greedy"),
    pytest.param(lambda h, w: topk_scores(h, w, 40, interpret=False),
                 (_HD, _W), id="k40"),
    pytest.param(lambda h, w: topk_scores(h, w, 40, interpret=False,
                                          return_lse=True),
                 (_HD, _W), id="k40_lse"),
    pytest.param(lambda h, w, m: topk_scores(h, w, 40, interpret=False,
                                             allowed_mask=m),
                 (_HD, _W, ((B_DECODE, V), jnp.int8)), id="k40_mask"),
    pytest.param(lambda h, w, s: topk_scores(h, w, 40, interpret=False,
                                             w_scale=s),
                 (_HD, ((V, D), jnp.int8), _SCALE), id="k40_int8_head"),
])
def test_sample_topk_compiles(one_chip, fn, shapes):
    _compile(fn, one_chip, shapes, ["sample_topk"])


def test_score_tokens_compiles(one_chip):
    _compile(lambda h, w, ids: score_stats(h, w, ids, interpret=False),
             one_chip, (_HD, _W, ((B_DECODE, 4), jnp.int32)),
             ["score_tokens"])


@pytest.mark.parametrize("quantized", [False, True], ids=["bf16", "int8"])
def test_paged_attn_compiles(one_chip, quantized):
    nb = MAX_LEN // BLOCK
    pool = (B_DECODE * nb + 1, BLOCK, NKV, HD)
    page = (pool, jnp.int8 if quantized else jnp.bfloat16)
    shapes = [((B_DECODE, 1, NQ, HD), jnp.bfloat16), page, page,
              ((B_DECODE, nb), jnp.int32), ((B_DECODE,), jnp.int32)]
    if quantized:
        scale = (pool[:3] + (1,), jnp.float32)
        shapes += [scale, scale]

    def fn(q, kp, vp, table, lens, *scales):
        ks, vs = scales if scales else (None, None)
        return pallas_paged_attention(q, kp, vp, table, lens, kp_scale=ks,
                                      vp_scale=vs, interpret=False)
    _compile(fn, one_chip, shapes, ["paged_attn"])


@pytest.mark.parametrize("phase", ["fwd", "bwd"])
def test_flash_attn_compiles(one_chip, phase):
    """The training cell's attention core: 4 x 4096 tokens, 16 query and
    8 KV heads of 128, f32 activations, the block the shapes choose."""
    block = flash_attn.choose_block(T_ATTN, HD, NQ // NKV)
    q = ((B_ATTN, T_ATTN, NQ, HD), jnp.float32)
    kv = ((B_ATTN, T_ATTN, NKV, HD), jnp.float32)
    if phase == "fwd":
        _compile(lambda q, k, v: flash_attn.flash_fwd(
            q, k, v, block, interpret=False), one_chip, (q, kv, kv),
            ["flash_attn_fwd"])
    else:
        stats = ((B_ATTN, NKV, NQ // NKV, T_ATTN), jnp.float32)
        _compile(lambda q, k, v, o, lse, do: flash_attn.flash_bwd(
            q, k, v, o, lse, do, block, interpret=False), one_chip,
            (q, kv, kv, q, stats, q), ["flash_attn_dkv", "flash_attn_dq"])
