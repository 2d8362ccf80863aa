"""Pure-jnp oracle for the fused projection+CE kernels.

Materializes the full logits tensor (exactly what the paper avoids) and
computes the same per-row statistics and gradients the kernels produce.
Used only by tests and as documentation of the exact semantics.
"""

from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp

from repro.core.types import LossConfig

_NEG_INF = float("-inf")


def _logits(h, w, cfg: LossConfig):
    z = jnp.dot(h.astype(jnp.float32), w.astype(jnp.float32).T,
                preferred_element_type=jnp.float32)
    if cfg.logit_softcap is not None:
        cap = jnp.float32(cfg.logit_softcap)
        z = cap * jnp.tanh(z / cap)
    valid = cfg.resolve_vocab(w.shape[0])
    col = jnp.arange(w.shape[0])
    z = jnp.where(col[None, :] < valid, z, _NEG_INF)
    return z, valid


def ref_stats(h, w, y, cfg: LossConfig) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """(lse, z_target, z_sum) per row — oracle for the forward kernel."""
    z, valid = _logits(h, w, cfg)
    lse = jax.nn.logsumexp(z, axis=-1)
    col = jnp.arange(w.shape[0])
    # valid-column guard matches the kernels: a target pointing at a masked
    # pad column contributes 0 (the TP psum-merge convention), never -inf
    is_tgt = (col[None, :] == y[:, None]) & (col[None, :] < valid)
    z_tgt = jnp.sum(jnp.where(is_tgt, z, 0.0), axis=-1)
    z_sum = jnp.sum(jnp.where(col[None, :] < valid, z, 0.0), axis=-1)
    return lse, z_tgt, z_sum


def ref_grads(h, w, y, lse, gamma, p_coeff, cfg: LossConfig):
    """(dH, dW) — oracle for the backward kernel.

    gamma:   per-row upstream scale Γ_n           (0 for ignored rows)
    p_coeff: per-row coefficient of the softmax    Γ_n (1 + 2 λ_z lse_n)
    """
    z, valid = _logits(h, w, cfg)
    p = jnp.exp(z - lse[:, None])
    col = jnp.arange(w.shape[0])
    onehot = (col[None, :] == y[:, None]).astype(jnp.float32)
    eps = jnp.float32(cfg.label_smoothing)
    g = (p_coeff[:, None] * p
         - gamma[:, None] * ((1.0 - eps) * onehot + eps / valid))
    if cfg.logit_softcap is not None:
        cap = jnp.float32(cfg.logit_softcap)
        g = g * (1.0 - (z / cap) ** 2)
    g = jnp.where(col[None, :] < valid, g, 0.0)
    dh = jnp.dot(g, w.astype(jnp.float32), preferred_element_type=jnp.float32)
    dw = jnp.dot(g.T, h.astype(jnp.float32), preferred_element_type=jnp.float32)
    return dh, dw
