"""Candidate-token scoring Pallas kernel for logits-free verification."""

from repro.kernels.score_tokens.ops import pallas_score_tokens
from repro.kernels.score_tokens.kernel import score_stats
from repro.kernels.score_tokens.ref import streaming_score

__all__ = ["pallas_score_tokens", "score_stats", "streaming_score"]
