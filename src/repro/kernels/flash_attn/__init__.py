"""Flash-attention Pallas TPU kernels for the training step's causal GQA
attention core (forward, dK/dV, dQ)."""

from repro.kernels.flash_attn.kernel import (choose_block, flash_bwd,
                                             flash_fwd)

__all__ = ["choose_block", "flash_fwd", "flash_bwd"]
