"""Architecture registry base types + the assigned input-shape grid.

Every assigned architecture provides `get_config()` (exact public config)
and `reduced()` (same family, tiny dims — used by CPU smoke tests).
`input_specs(arch, shape)` builds ShapeDtypeStruct stand-ins for the
dry-run (no allocation).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict

import jax
import jax.numpy as jnp

from repro.core.types import LossConfig

# ---------------------------------------------------------------------------
# multi-token prediction (DESIGN.md §7)
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class MTPConfig:
    """Multi-token-prediction heads over the shared trunk (Gloeckle et al.).

    Horizon 0 is the trunk's own next-token prediction (always present);
    head h in 1..n_heads predicts the token at offset h+1 from the same
    position, through `head_depth` residual MLP blocks applied to the
    trunk's final hidden state and the SHARED lm_head — so every horizon's
    loss runs through the same fused-CE kernels with one BlockPlan.

    Attributes:
      n_heads: number of extra future-token heads (0 disables MTP).
      head_depth: residual MLP blocks per head.
      d_ff: head MLP hidden width; 0 -> 2 * d_model.
      loss_weights: per-head loss weights (horizon 1..n_heads); () means
        1.0 each.  A weight of exactly 0.0 statically drops that horizon
        from the total loss (its gradient contribution is identically
        zero), while its metrics are still reported.
      track_accuracy: also report per-horizon top-1 accuracy, computed
        with the streaming (logits-free) top-1 scan under stop_gradient.
        Off by default: the extra scan is a full vocab sweep per horizon
        per step — loss-order compute bought purely for a metric.
    """

    n_heads: int = 0
    head_depth: int = 1
    d_ff: int = 0
    loss_weights: tuple = ()
    track_accuracy: bool = False

    def __post_init__(self):
        if self.n_heads < 0:
            raise ValueError("mtp.n_heads must be >= 0")
        if self.head_depth < 1:
            raise ValueError("mtp.head_depth must be >= 1")
        if self.loss_weights and len(self.loss_weights) != self.n_heads:
            raise ValueError(
                f"mtp.loss_weights has {len(self.loss_weights)} entries "
                f"for {self.n_heads} heads (use () for all-1.0)")
        if any(w < 0 for w in self.loss_weights):
            raise ValueError("mtp.loss_weights must be >= 0")

    def resolved_weights(self) -> tuple:
        return tuple(self.loss_weights) or (1.0,) * self.n_heads

    def resolved_d_ff(self, d_model: int) -> int:
        return self.d_ff or 2 * d_model


# ---------------------------------------------------------------------------
# shape grid (assignment: LM shapes are seq_len x global_batch)
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class ShapeSpec:
    name: str
    seq_len: int
    global_batch: int
    kind: str            # 'train' | 'prefill' | 'decode'


SHAPES: Dict[str, ShapeSpec] = {
    "train_4k": ShapeSpec("train_4k", 4096, 256, "train"),
    "prefill_32k": ShapeSpec("prefill_32k", 32768, 32, "prefill"),
    "decode_32k": ShapeSpec("decode_32k", 32768, 128, "decode"),
    "long_500k": ShapeSpec("long_500k", 524288, 1, "decode"),
}

# encoder frame length used for enc-dec serve shapes (decoder gets seq_len)
ENCDEC_SERVE_ENC_LEN = 4096


@dataclasses.dataclass(frozen=True)
class Arch:
    """One selectable architecture (--arch <id>)."""

    arch_id: str
    family: str                   # transformer | xlstm | griffin | encdec
    cfg: Any                      # family config dataclass
    tags: tuple = ()              # ('moe',), ('ssm',), ...
    vocab_pad_multiple: int = 256  # lm_head rows padded to this multiple
    mtp: MTPConfig = MTPConfig()   # multi-token prediction heads

    @property
    def vocab_size(self) -> int:
        return self.cfg.vocab_size

    @property
    def padded_vocab(self) -> int:
        m = self.vocab_pad_multiple
        return -(-self.vocab_size // m) * m

    @property
    def sub_quadratic(self) -> bool:
        return self.family in ("xlstm", "griffin")

    def loss_config(self, **kw) -> LossConfig:
        kw.setdefault("valid_vocab", self.vocab_size)
        return LossConfig(**kw)

    def supports(self, shape: str) -> bool:
        s = SHAPES[shape]
        if s.name == "long_500k":
            return self.sub_quadratic     # spec: full-attention archs skip
        return True


def with_mtp(arch: Arch, n_heads: int, **kw) -> Arch:
    """`arch` with an `MTPConfig(n_heads=n_heads, **kw)` block attached."""
    return dataclasses.replace(arch, mtp=MTPConfig(n_heads=n_heads, **kw))


def _ids(shape, dtype=jnp.int32):
    return jax.ShapeDtypeStruct(shape, dtype)


def _f(shape, dtype=jnp.bfloat16):
    return jax.ShapeDtypeStruct(shape, dtype)


def input_specs(arch: Arch, shape_name: str) -> Dict[str, Any]:
    """ShapeDtypeStruct stand-ins for every model input of this cell.

    train:    tokens + targets (+ frontend embeds for vlm/audio stubs)
    prefill:  tokens (+ frontend embeds)
    decode:   one new token; the KV/recurrent cache specs come separately
              from `serve.cache_specs` (they are step state, not input).
    """
    s = SHAPES[shape_name]
    b = s.global_batch
    d = arch.cfg.d_model
    cdt = jnp.dtype(getattr(arch.cfg, "compute_dtype", "float32"))

    if arch.family == "encdec":
        enc_len = s.seq_len if s.kind == "train" else ENCDEC_SERVE_ENC_LEN
        if s.kind == "train":
            return {"frontend_embeds": _f((b, enc_len, d), cdt),
                    "tokens": _ids((b, s.seq_len)),
                    "targets": _ids((b, s.seq_len))}
        if s.kind == "prefill":
            return {"frontend_embeds": _f((b, enc_len, d), cdt),
                    "tokens": _ids((b, s.seq_len))}
        return {"tokens": _ids((b, 1))}

    front = getattr(arch.cfg, "frontend_len", 0)
    if s.kind == "train":
        spec = {"tokens": _ids((b, s.seq_len - front)),
                "targets": _ids((b, s.seq_len))}
        if front:
            spec["frontend_embeds"] = _f((b, front, d), cdt)
        return spec
    if s.kind == "prefill":
        spec = {"tokens": _ids((b, s.seq_len - front))}
        if front:
            spec["frontend_embeds"] = _f((b, front, d), cdt)
        return spec
    return {"tokens": _ids((b, 1))}
