"""Distributed behaviors under 8 forced host devices (subprocess: the
device count must be fixed before jax initializes, and the main test
process must keep its single real device)."""

import os
import subprocess
import sys
import textwrap

import pytest

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(body: str, timeout=560):
    script = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.launch.mesh import make_local_mesh
        mesh = make_local_mesh(2, 4)
    """) + textwrap.dedent(body)
    env = dict(os.environ, PYTHONPATH=os.path.join(_ROOT, "src"))
    res = subprocess.run([sys.executable, "-c", script], env=env,
                         capture_output=True, text=True, timeout=timeout)
    assert res.returncode == 0, f"STDOUT:{res.stdout}\nSTDERR:{res.stderr}"
    return res.stdout


@pytest.mark.slow
def test_sharded_loss_all_layouts_and_impls():
    out = _run("""
        from repro.core import LossConfig, canonical_loss
        from repro.core.sharded import make_sharded_loss
        k1,k2,k3 = jax.random.split(jax.random.PRNGKey(0),3)
        N,d,V = 64, 32, 256
        h = jax.random.normal(k1,(N,d)); w = jax.random.normal(k2,(V,d))*0.05
        y = jax.random.randint(k3,(N,),0,250).at[5].set(-100)
        cfg = LossConfig(block_v=64, valid_vocab=250, label_smoothing=0.05,
                         z_loss=1e-4)
        ref = canonical_loss(h,w,y,cfg)
        gref = jax.grad(lambda h,w: canonical_loss(h,w,y,cfg),(0,1))(h,w)
        for layout in ("2d","sp_gather"):
            for impl in ("streaming","pallas"):
                f = make_sharded_loss(mesh, cfg, rows_axes=("data",),
                                      layout=layout, impl=impl)
                rows_ax = ("data","model") if layout=="sp_gather" else ("data",)
                hs = jax.device_put(h, NamedSharding(mesh, P(rows_ax, None)))
                ws = jax.device_put(w, NamedSharding(mesh, P("model", None)))
                ys = jax.device_put(y, NamedSharding(mesh, P(rows_ax)))
                np.testing.assert_allclose(np.asarray(jax.jit(f)(hs,ws,ys)),
                                           np.asarray(ref), rtol=2e-5)
                g = jax.jit(jax.grad(f,(0,1)))(hs,ws,ys)
                np.testing.assert_allclose(np.asarray(g[0]),
                    np.asarray(gref[0]), rtol=5e-4, atol=1e-6)
                np.testing.assert_allclose(np.asarray(g[1]),
                    np.asarray(gref[1]), rtol=5e-4, atol=1e-6)
                print("ok", layout, impl)
        print("DONE")
    """)
    assert "DONE" in out


@pytest.mark.slow
def test_moe_ep_and_embed_lookup_shardmap():
    out = _run("""
        from repro.models.moe import MoEConfig, init_moe, moe_layer
        from repro.models.layers import embed_lookup
        from repro.sharding.rules import AxisRules
        rules = AxisRules(mesh=mesh)
        cfg = MoEConfig(d_model=32, d_ff=16, num_experts=8, top_k=2)
        params = init_moe(jax.random.PRNGKey(0), cfg)
        x = jax.random.normal(jax.random.PRNGKey(1), (4, 24, 32))
        ref, aux_ref = moe_layer(params, x, cfg)
        xs = jax.device_put(x, NamedSharding(mesh, P("data", None, None)))
        out, aux = jax.jit(lambda p, x: moe_layer(p, x, cfg,
                                                  shard=rules.shard))(params, xs)
        np.testing.assert_allclose(np.asarray(ref), np.asarray(out),
                                   rtol=2e-5, atol=2e-5)
        # embed lookup
        table = jax.random.normal(jax.random.PRNGKey(2), (50, 16))
        toks = jax.random.randint(jax.random.PRNGKey(3), (4, 6), 0, 50)
        a = table[toks]
        b = jax.jit(lambda t, k: embed_lookup(t, k, shard=rules.shard))(
            table, toks)
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-6)
        # grads flow through the shard_map lookup
        g = jax.jit(jax.grad(lambda t: jnp.sum(
            embed_lookup(t, toks, shard=rules.shard) ** 2)))(table)
        gr = jax.grad(lambda t: jnp.sum(t[toks] ** 2))(table)
        np.testing.assert_allclose(np.asarray(g), np.asarray(gr), rtol=1e-5)
        print("DONE")
    """)
    assert "DONE" in out


@pytest.mark.slow
def test_compressed_psum_and_elastic_reshard():
    out = _run("""
        from functools import partial
        from repro.distributed.compression import (init_residuals,
            compressed_psum_tree)
        from repro.distributed.elastic import reshard, plan_batch
        from repro.sharding.rules import AxisRules, param_shardings

        # ---- compressed mean-all-reduce over 'data' ----
        grads = {"w": jax.random.normal(jax.random.PRNGKey(0), (2, 16, 8))}
        res = {"w": jnp.zeros((2, 16, 8))}
        def sync(g, r):
            return compressed_psum_tree(g, r, "data")
        f = jax.shard_map(sync, mesh=mesh,
                          in_specs=({"w": P("data", None, None)},
                                    {"w": P("data", None, None)}),
                          out_specs=({"w": P("data", None, None)},
                                     {"w": P("data", None, None)}),
                          check_vma=False)
        mean, new_res = jax.jit(f)(grads, res)
        # exact mean within int8 quantization error bound
        exact = np.mean(np.asarray(grads["w"]), axis=0, keepdims=True)
        exact = np.broadcast_to(exact, (2, 16, 8))
        err = np.abs(np.asarray(mean["w"]) - exact).max()
        scale = np.abs(np.asarray(grads["w"])).max() / 127.0
        assert err <= 2 * scale + 1e-6, (err, scale)
        # error feedback: quantization residual is carried, not lost
        assert float(jnp.max(jnp.abs(new_res["w"]))) > 0

        # ---- elastic reshard across mesh shapes ----
        params = {"w": jax.random.normal(jax.random.PRNGKey(1), (16, 32))}
        r1 = AxisRules(mesh=mesh)
        p1 = reshard(params, param_shardings(params, r1))
        mesh2 = make_local_mesh(4, 2)
        r2 = AxisRules(mesh=mesh2)
        p2 = reshard(p1, param_shardings(params, r2))
        np.testing.assert_allclose(np.asarray(p2["w"]),
                                   np.asarray(params["w"]))
        assert plan_batch(32, mesh2)["per_shard"] == 8
        print("DONE")
    """)
    assert "DONE" in out


@pytest.mark.slow
def test_small_mesh_train_step_compiles_and_runs():
    """A true multi-device train step: lower+compile+EXECUTE on the 2x4
    mesh with the sharded (paper-TP) loss — the miniature of the dry-run."""
    out = _run("""
        from repro.models.registry import get_arch
        from repro.sharding.rules import AxisRules
        from repro.train.state import state_shardings
        from repro.train.step import TrainConfig, build_train_step
        arch = get_arch("qwen3-0.6b", reduced=True)
        rules = AxisRules(mesh=mesh)
        tc = TrainConfig(optimizer="adamw", loss_impl="sharded",
                         loss_block_v=64, peak_lr=1e-3)
        init_fn, step_fn = build_train_step(arch, tc, rules)
        state = init_fn(jax.random.PRNGKey(0))
        sh = state_shardings(state, rules)
        state = jax.device_put(state, sh)
        jstep = jax.jit(step_fn, in_shardings=(sh, None),
                        out_shardings=(sh, None), donate_argnums=(0,))
        B, T = 8, 32
        ks = jax.random.split(jax.random.PRNGKey(1), 2)
        batch = {"tokens": jax.random.randint(ks[0], (B, T), 0, 512),
                 "targets": jax.random.randint(ks[1], (B, T), 0, 512)}
        losses = []
        for i in range(8):
            state, m = jstep(state, batch)
            losses.append(float(m["loss"]))
        assert all(np.isfinite(l) for l in losses)
        assert losses[-1] < losses[0], losses   # overfits one batch
        print("DONE", losses[0], losses[-1])
    """)
    assert "DONE" in out
