"""qwen3-0.6b [dense] — 28L d_model=1024 16H (GQA kv=8, head_dim=128,
qk_norm) d_ff=3072 vocab=151936.  [hf:Qwen/Qwen3 family]

The vocab-dominated regime: the embedding + lm_head hold ~50% of all
parameters — the paper's best case.
"""

from repro.configs.base import Arch
from repro.models.transformer import TransformerConfig


def get_config(**overrides) -> Arch:
    """Qwen3-0.6B as it is trained: f32 master weights (and so f32 AdamW
    moments) under bf16 products, activations and residual stream.
    Weights stored in bf16 do not train: an AdamW update under half a
    bf16 step of its weight (1.2e-4 at 0.03) is rounded away, and at a
    warmup's learning rates that is nearly every update.  The forward
    casts the masters to bf16 once a step (`registry.cast_params`)."""
    cfg = TransformerConfig(
        name="qwen3-0.6b",
        d_model=1024, n_layers=28,
        num_heads=16, num_kv_heads=8, head_dim=128,
        d_ff=3072, vocab_size=151936,
        qk_norm=True, rope_theta=1.0e6,
        param_dtype="float32", compute_dtype="bfloat16",
        **overrides)
    return Arch("qwen3-0.6b", "transformer", cfg, tags=("dense",))


def reduced() -> Arch:
    cfg = TransformerConfig(
        name="qwen3-0.6b-reduced",
        d_model=64, n_layers=2,
        num_heads=4, num_kv_heads=2, head_dim=16,
        d_ff=96, vocab_size=512,
        qk_norm=True, chunk_q=32, chunk_k=32)
    return Arch("qwen3-0.6b", "transformer", cfg, tags=("dense",),
                vocab_pad_multiple=16)
