"""Operations and bytes of the decoder's work, from its shapes alone.

`spec` is `bench.weights.model_spec` of a configuration.  Every count is
of the algorithm's necessary work, never of what an implementation
happens to do: recomputation, padding and re-reads are not counted, so
no implementation can read above its roofline.
"""

from __future__ import annotations

from typing import Dict, Tuple


def matmul_params(spec: Dict) -> int:
    """Weights that take part in a matrix product per token: the four
    attention projections, the three MLP matrices and the lm_head over
    the real vocabulary (the embedding is a lookup)."""
    d, nq, nkv, hd, f = (spec[k] for k in
                         ("d", "heads", "kv_heads", "head_dim", "ff"))
    per_layer = 2 * d * nq * hd + 2 * d * nkv * hd + 3 * d * f
    return spec["layers"] * per_layer + spec["vocab"] * d


def train_flops_per_token(spec: Dict, seq_len: int) -> float:
    """Model FLOPs of one trained token in a causal sequence of
    `seq_len`: 6 per matmul weight, plus causal attention (the two
    products over the (T + 1) / 2 keys a query sees on average, forward
    and backward)."""
    attn = 6.0 * spec["heads"] * spec["head_dim"] * (seq_len + 1) \
        * spec["layers"]
    return 6.0 * matmul_params(spec) + attn


def least_time(flops: float, nbytes: float, peaks: Dict
               ) -> Tuple[float, str]:
    """(the least time the chip could take, which bound sets it)."""
    tc = flops / peaks["bf16_flops"]
    tb = nbytes / peaks["hbm_bytes_per_s"]
    return (tc, "compute") if tc >= tb else (tb, "bytes")


def fused_ce_work(rows: int, vocab: int, d: int, itemsize: int = 2
                  ) -> Tuple[float, float]:
    """The loss's forward and backward through the vocabulary: three
    (rows, vocab, d) products (logits, dH, dW), one read of h and W and
    one write of dH and dW."""
    return 6.0 * rows * vocab * d, 2.0 * (rows + vocab) * d * itemsize

