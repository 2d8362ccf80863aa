"""Reusable gradient-oracle harness for the fused-CE implementation family.

Every backward-parity test in the suite (exact grads, filtered grads,
convergence, hypothesis properties) compares an implementation's
`jax.grad` against the SAME canonical two-stage oracle on the SAME
problem construction.  Centralizing the harness here keeps those grids
consistent: a new impl (or a new knob like `grad_filter_eps`) gets its
oracle coverage by parametrizing over `IMPLS`/`CFGS`, not by re-deriving
problem builders per file.

Exports
-------
IMPLS / SHAPES / CFGS       the canonical test grid
make_problem(...)           (h, w, y) with ignore-masked rows; `peaked`
                            concentrates the softmax so gradient
                            filtering has tiles to skip
oracle_grads(h, w, y, cfg)  canonical-loss f32 jax.grad — THE reference
impl_grads(...)             jax.grad through `fused_cross_entropy`
sharded_grads(...)          jax.grad through `make_sharded_loss`
mesh_1x1()                  single-device ("data", "model") mesh
max_abs_dev(ga, gb)         worst |a - b| across the (dh, dw) pair
"""

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh

from repro.core import LossConfig, canonical_loss, fused_cross_entropy
from repro.core.sharded import make_sharded_loss

IMPLS = ("canonical", "streaming", "pallas")

# (n, v, d): ragged row/vocab counts exercise partial tiles in every impl
SHAPES = [(16, 128, 32), (33, 100, 24)]

CFGS = {
    "base": LossConfig(block_v=64),
    "softcap": LossConfig(block_v=64, logit_softcap=12.0),
    "smooth_z": LossConfig(block_v=48, label_smoothing=0.1, z_loss=1e-4),
    "padded": LossConfig(block_v=64, valid_vocab=90),
    "sum": LossConfig(block_v=64, reduction="sum"),
}


def make_problem(n, v, d, dtype=jnp.float32, seed=0, valid=None,
                 ignore_every=5, peaked=0.0, target_band=None):
    """Synthetic (h, w, y) for oracle comparisons.

    `ignore_every=k` masks every k-th row with the ignore index (0/None
    disables).  `peaked=s > 0` sets ``h = s * w[y] + noise`` — the
    softmax concentrates on the target, which is what gives the gradient
    filter low-mass tiles to skip; `target_band=(lo, hi)` additionally
    confines targets to a vocab range so whole off-band tiles drain.
    """
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(seed), 3)
    w = (jax.random.normal(k2, (v, d)) * (0.5 if peaked else 0.05)
         ).astype(dtype)
    lo, hi = target_band if target_band else (0, valid or v)
    y = jax.random.randint(k3, (n,), lo, hi)
    if peaked:
        noise = 0.1 * jax.random.normal(k1, (n, d))
        h = (peaked * w[y].astype(jnp.float32) + noise).astype(dtype)
    else:
        h = jax.random.normal(k1, (n, d)).astype(dtype)
    if ignore_every:
        # ignore-masked rows: the oracle AND the kernels must zero their
        # gradient contribution and renormalize the 'mean' denominator
        y = y.at[::ignore_every].set(LossConfig().ignore_index)
    return h, w, y


def oracle_grads(h, w, y, cfg):
    """f32 canonical-loss jax.grad — the reference every impl must match."""
    return jax.grad(
        lambda h, w: canonical_loss(h.astype(jnp.float32),
                                    w.astype(jnp.float32), y, cfg),
        (0, 1))(h, w)


def impl_grads(h, w, y, cfg, impl, plan=None):
    """(dh, dw) through the public `fused_cross_entropy` entry point."""
    return jax.grad(
        lambda h, w: fused_cross_entropy(h, w, y, impl=impl, cfg=cfg,
                                         plan=plan),
        (0, 1))(h, w)


def mesh_1x1():
    dev = np.asarray(jax.devices()[:1]).reshape(1, 1)
    return Mesh(dev, ("data", "model"))


def sharded_grads(h, w, y, cfg, layout="2d", impl="streaming", mesh=None,
                  plan=None):
    """(dh, dw) through the shard_map custom_vjp builder (1x1 mesh by
    default: identical collective schedule, single shard)."""
    loss_fn = make_sharded_loss(mesh or mesh_1x1(), cfg,
                                rows_axes=("data",), vocab_axis="model",
                                layout=layout, impl=impl, plan=plan)
    return jax.grad(loss_fn, (0, 1))(h, w, y)


def max_abs_dev(ga, gb):
    """Worst absolute elementwise deviation across the (dh, dw) pair."""
    return max(
        float(jnp.max(jnp.abs(jnp.asarray(a, jnp.float32)
                              - jnp.asarray(b, jnp.float32))))
        for a, b in zip(ga, gb))


# the tolerance every gradient comparison against the oracle is held to
GRAD_RTOL, GRAD_ATOL = 3e-4, 1e-5


def assert_grads_close(ga, gb, rtol=GRAD_RTOL, atol=GRAD_ATOL):
    np.testing.assert_allclose(np.asarray(ga[0], np.float32),
                               np.asarray(gb[0], np.float32),
                               rtol=rtol, atol=atol)
    np.testing.assert_allclose(np.asarray(ga[1], np.float32),
                               np.asarray(gb[1], np.float32),
                               rtol=rtol, atol=atol)


def assert_grads_equal(ga, gb):
    """Bitwise equality — used for the eps=0 no-regression guarantee."""
    np.testing.assert_array_equal(np.asarray(ga[0], np.float32),
                                  np.asarray(gb[0], np.float32))
    np.testing.assert_array_equal(np.asarray(ga[1], np.float32),
                                  np.asarray(gb[1], np.float32))
