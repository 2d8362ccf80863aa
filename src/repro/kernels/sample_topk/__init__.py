"""Streaming top-k Pallas kernel for logits-free decode sampling."""

from repro.kernels.sample_topk.ops import pallas_topk
from repro.kernels.sample_topk.kernel import topk_scores

__all__ = ["pallas_topk", "topk_scores"]
