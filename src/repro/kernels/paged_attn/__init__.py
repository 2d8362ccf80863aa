from repro.kernels.paged_attn.kernel import pallas_paged_attention

__all__ = ["pallas_paged_attention"]
