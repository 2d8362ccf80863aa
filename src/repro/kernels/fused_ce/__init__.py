"""Fused output-projection + cross-entropy Pallas TPU kernels."""

from repro.kernels.fused_ce.ops import pallas_loss
from repro.kernels.fused_ce.kernel import fwd_stats, bwd_grads

__all__ = ["pallas_loss", "fwd_stats", "bwd_grads"]
