"""The flash-attention kernels (`kernels/flash_attn`) against the jnp
blockwise loops, in interpret mode, and the dispatch that picks between
them (`models/attention.py::_kernel_block`).

The kernels round every product's operands to bf16 with f32
accumulation, as XLA's default precision does on the TPU; the CPU runs
the jnp path's products in f32.  So the inputs are rounded to bf16
first (the kernels' own rounding of them is then exact) and the gap
left is the rounding of the probability and score-gradient tiles: one
bf16 pass, held to 1% of the largest reference entry.  The row
log-sum-exp involves no rounded product and is held to f32.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs
from repro.kernels import flash_attn
from repro.models import attention as A
from repro.models import transformer as T

B, SEQ, HD = 2, 256, 128
ONE_PASS = 1e-2


def _bf16(x):
    return x.astype(jnp.bfloat16).astype(jnp.float32)


def _inputs(nq, nkv, t=SEQ, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    q = _bf16(jax.random.normal(ks[0], (B, t, nq, HD)))
    k = _bf16(jax.random.normal(ks[1], (B, t, nkv, HD)))
    v = _bf16(jax.random.normal(ks[2], (B, t, nkv, HD)))
    ct = _bf16(jax.random.normal(ks[3], (B, t, nq, HD)))
    return q, k, v, ct


def _cfg(nq, nkv, **kw):
    return A.AttnConfig(d_model=nq * HD, num_heads=nq, num_kv_heads=nkv,
                        head_dim=HD, chunk_q=128, chunk_k=128, **kw)


def _close(got, want, tol):
    gap = float(jnp.max(jnp.abs(got - want)))
    assert gap <= tol * float(jnp.max(jnp.abs(want))), gap


@pytest.mark.parametrize("nq,nkv,block", [
    pytest.param(4, 2, 128, id="gqa2"),
    pytest.param(2, 2, 128, id="mha"),
    pytest.param(4, 2, 256, id="gqa2_one_block"),
])
def test_kernels_match_the_jnp_path(nq, nkv, block):
    q, k, v, ct = _inputs(nq, nkv)
    cfg = _cfg(nq, nkv)
    out, lse = flash_attn.flash_fwd(q, k, v, block, interpret=True)
    out_ref, lse_ref = A._flash_fwd_impl(q, k, v, cfg, SEQ)
    assert out.dtype == lse.dtype == jnp.float32
    assert lse.shape == lse_ref.shape == (B, nkv, nq // nkv, SEQ)
    _close(out, out_ref, ONE_PASS)
    np.testing.assert_allclose(lse, lse_ref, rtol=1e-6, atol=1e-5)
    grads = flash_attn.flash_bwd(q, k, v, out, lse, ct, block,
                                 interpret=True)
    want = A._flash_bwd_impl(q, k, v, out_ref, lse_ref, ct, cfg, SEQ)
    for g, w in zip(grads, want):
        assert g.dtype == jnp.float32 and g.shape == w.shape
        _close(g, w, ONE_PASS)


def test_kernels_round_q_k_v_to_bf16():
    """q, k and v a little off the bf16 grid give the results of their
    bf16 roundings, bit for bit: the kernels round them as operands and
    use them nowhere else."""
    q, k, v, ct = _inputs(4, 2, seed=1)
    off = [x + 1e-4 * jnp.abs(x) * jax.random.normal(
        jax.random.PRNGKey(i), x.shape) for i, x in enumerate((q, k, v))]
    exact = flash_attn.flash_fwd(q, k, v, 128, interpret=True)
    for a, b in zip(exact, flash_attn.flash_fwd(*off, 128, interpret=True)):
        np.testing.assert_array_equal(a, b)
    grads = flash_attn.flash_bwd(q, k, v, *exact, ct, 128, interpret=True)
    for a, b in zip(grads, flash_attn.flash_bwd(*off, *exact, ct, 128,
                                                interpret=True)):
        np.testing.assert_array_equal(a, b)


def test_choose_block_from_the_shapes():
    assert flash_attn.choose_block(4096, 128, 2) == 1024
    assert flash_attn.choose_block(256, 128, 2) == 256
    assert flash_attn.choose_block(640, 128, 2) == 128
    assert flash_attn.choose_block(4096, 256, 8) == 512
    assert flash_attn.choose_block(200, 128, 2) is None
    assert flash_attn.choose_block(4096, 64, 2) is None


@pytest.fixture
def on_tpu(monkeypatch):
    """The dispatch as a TPU would see it; the kernels then run in
    interpret mode on the CPU."""
    monkeypatch.setattr(A, "_on_tpu", lambda: True)


def _layer(cfg, t=SEQ):
    params = A.init_attention(jax.random.PRNGKey(0), cfg)
    x = jax.random.normal(jax.random.PRNGKey(1), (B, t, cfg.d_model))
    return params, x


def test_training_call_takes_the_kernels_on_a_tpu(on_tpu, monkeypatch):
    """The layer's value and its gradients through `jax.grad` (the
    kernels' custom VJP) agree with the jnp path's."""
    cfg = _cfg(4, 2, qk_norm=True)
    params, x = _layer(cfg)
    ct = jax.random.normal(jax.random.PRNGKey(2), x.shape)

    def loss(params, x):
        return jnp.sum(A.attention_layer(params, x, cfg)[0] * ct)

    grad = jax.value_and_grad(loss, argnums=(0, 1))
    with obs.capture(trace=False) as (reg, _):
        got = jax.jit(grad)(params, x)
        counts = [reg.get(n).value for n in obs.ATTN_COUNTERS]
    assert counts == [1, 0]
    monkeypatch.setattr(A, "_on_tpu", lambda: False)
    want = jax.jit(grad)(params, x)
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        _close(g, w, ONE_PASS)


@pytest.mark.parametrize("kw,t,shard", [
    pytest.param({"window": 64}, SEQ, None, id="window"),
    pytest.param({"attn_softcap": 30.0}, SEQ, None, id="softcap"),
    pytest.param({"causal": False}, SEQ, None, id="non_causal"),
    pytest.param({}, 200, None, id="seq_off_block"),
    pytest.param({}, SEQ, lambda y, *axes: y, id="sharded"),
])
def test_training_call_keeps_the_jnp_path(on_tpu, kw, t, shard):
    cfg = _cfg(4, 2, **kw)
    params, x = _layer(cfg, t)
    q, k, _ = A._project_qkv(params, x, jnp.arange(t)[None], cfg)
    assert A._kernel_block(q, k, cfg, shard) is None
    with obs.capture(trace=False) as (reg, _):
        jax.eval_shape(lambda p, x: A.attention_layer(p, x, cfg,
                                                      shard=shard), params, x)
        assert [reg.get(n).value for n in obs.ATTN_COUNTERS] == [0, 1]


def test_off_a_tpu_the_training_call_keeps_the_jnp_path():
    cfg = _cfg(4, 2)
    params, x = _layer(cfg)
    q, k, _ = A._project_qkv(params, x, jnp.arange(SEQ)[None], cfg)
    assert A._kernel_block(q, k, cfg) is None


def _caches(cfg):
    nb, bs = SEQ // 16, 16
    pool = jnp.zeros((B * nb + 1, bs, cfg.num_kv_heads, HD))
    paged = {"kp": pool, "vp": pool,
             "table": 1 + jnp.arange(B * nb).reshape(B, nb),
             "len": jnp.zeros((B,), jnp.int32)}
    return {"slab": A.init_cache(B, SEQ, cfg, jnp.float32),
            "int8": A.init_cache(B, SEQ, cfg, quantize=True),
            "ring": A.init_local_cache(B, SEQ, cfg, jnp.float32),
            "paged": paged}


@pytest.mark.parametrize("kind", ["slab", "int8", "ring", "paged"])
def test_every_cache_path_keeps_the_jnp_path(on_tpu, kind):
    """Serving prefills stay on the jnp recurrence, which
    `extend_attention` is bit-identical to."""
    cfg = _cfg(4, 2)
    params, x = _layer(cfg)
    cache = _caches(cfg)[kind]
    with obs.capture(trace=False) as (reg, _):
        _, new = jax.eval_shape(lambda p, x, c: A.attention_layer(
            p, x, cfg, cache=c), params, x, cache)
        assert [reg.get(n).value for n in obs.ATTN_COUNTERS] == [0, 1]
    assert jax.tree.structure(new) == jax.tree.structure(cache)


def test_train_step_trace_counts_one_kernel_site(on_tpu):
    """The layer scan's body is traced once: one kernel site, no jnp
    site, in a remat scan's gradient."""
    cfg = T.TransformerConfig(name="t", d_model=256, n_layers=2,
                              num_heads=2, num_kv_heads=1, head_dim=HD,
                              d_ff=512, vocab_size=512, qk_norm=True)
    params = jax.eval_shape(lambda: T.init_params(jax.random.PRNGKey(0),
                                                  cfg))
    tokens = jax.ShapeDtypeStruct((B, SEQ), jnp.int32)

    def loss(p, tok):
        h, aux, _ = T.forward(p, tok, cfg)
        return jnp.mean(h * h) + aux

    with obs.capture(trace=False) as (reg, _):
        jax.eval_shape(jax.grad(loss), params, tokens)
        assert [reg.get(n).value for n in obs.ATTN_COUNTERS] == [1, 0]


def test_enable_starts_the_attention_counters_at_zero():
    with obs.capture(trace=False) as (reg, _):
        report = obs.export.metrics_report(reg)["metrics"]
    for name in obs.ATTN_COUNTERS:
        assert report[name] == {"kind": "counter", "value": 0.0}
