"""Public API: fused output projection + cross-entropy loss.

    loss = fused_cross_entropy(h, w, targets, impl=..., cfg=LossConfig(...))

Implementations (all semantically identical, verified against each other):

  'canonical' — two-stage baseline, logits materialized (paper §3.1).
  'streaming' — pure-JAX chunked online-softmax (paper Alg. 1/2); any backend.
  'pallas'    — Pallas TPU kernel (interpret=True on CPU); BlockSpec-tiled.
  'auto'      — 'pallas' on TPU, 'streaming' elsewhere.

Inputs may be (B, T, d)/(B, T) or already flattened (N, d)/(N,).
"""

from __future__ import annotations

from typing import Optional

import jax

from repro.core.types import LossConfig, IGNORE_INDEX
from repro.core.canonical import canonical_loss
from repro.core.streaming import streaming_loss
from repro.core.windows import BlockPlan

__all__ = [
    "fused_cross_entropy",
    "LossConfig",
    "IGNORE_INDEX",
]

_IMPLS = ("auto", "canonical", "streaming", "pallas")


def _flatten(h: jax.Array, y: jax.Array):
    if h.ndim == 2:
        return h, y
    if h.ndim == 3:
        b, t, d = h.shape
        return h.reshape(b * t, d), y.reshape(b * t)
    raise ValueError(f"hidden states must be rank 2 or 3, got {h.shape}")


def default_impl() -> str:
    """The local loss 'auto' resolves to: the Pallas kernels on a TPU,
    the `lax.scan` streaming loss elsewhere."""
    return "pallas" if jax.default_backend() == "tpu" else "streaming"


def fused_cross_entropy(
    h: jax.Array,
    w: jax.Array,
    targets: jax.Array,
    *,
    impl: str = "auto",
    cfg: Optional[LossConfig] = None,
    plan: Optional[BlockPlan] = None,
) -> jax.Array:
    """Cross-entropy of `softmax(h @ w.T)` against `targets`, fused.

    Args:
      h: (B, T, d) or (N, d) final hidden states.
      w: (V, d) lm_head weight (row-major over vocab).
      targets: (B, T) or (N,) int target token ids, `cfg.ignore_index`
        marking masked positions.
      impl: one of 'auto' | 'canonical' | 'streaming' | 'pallas'.
      cfg: LossConfig (reduction, label smoothing, z-loss, softcap, padding).
      plan: optional `BlockPlan` (DESIGN.md §3) — the Pallas tile shape /
        streaming window.  Ignored by 'canonical' (no tiling); `None` lets
        each impl resolve its own default (pallas: `choose_blocks` of the
        shape).

    Returns:
      scalar loss ('mean'/'sum') or per-row losses ('none').
    """
    if impl not in _IMPLS:
        raise ValueError(f"impl must be one of {_IMPLS}, got {impl!r}")
    cfg = cfg or LossConfig()
    hf, yf = _flatten(h, targets)
    if impl == "auto":
        impl = default_impl()
    if impl == "canonical":
        out = canonical_loss(hf, w, yf, cfg)
    elif impl == "streaming":
        out = streaming_loss(hf, w, yf, cfg, plan=plan)
    else:  # pallas
        from repro.kernels.fused_ce.ops import pallas_loss  # lazy: optional dep
        out = pallas_loss(hf, w, yf, cfg, plan=plan)
    if cfg.reduction == "none" and targets.ndim > 1:
        out = out.reshape(targets.shape)
    return out
